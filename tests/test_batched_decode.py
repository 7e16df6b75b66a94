"""Compiled batched decode fast path (PR 3).

Covers: bit-for-bit parity of the scan-compiled ``batched_greedy_decode``
against the per-sequence host loop for all three paper models (ragged
prefix-padded batches included), the EOS done-masking semantics of the
scan, the Pallas attention backend vs the XLA reference on the Marian
batched paths, the rewritten GenerationSession (scan vs host loop,
post-EOS masking, per-sequence lengths, ragged prompts, shape buckets),
and the engine's real batched execution (``submit_batch`` +
``build_executor(kind="batched")``).
"""

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.latency_model import DeviceProfile, LinearLatencyModel
from repro.core.length_regressor import LinearN2M
from repro.data.tokenizer import EOS_ID, PAD_ID
from repro.models.model import LM
from repro.nmt import (
    BiLSTMSeq2Seq,
    GRUSeq2Seq,
    MarianTransformer,
    RNNConfig,
    TransformerConfig,
    batched_greedy_decode,
)
from repro.runtime.engine import CollaborativeEngine, Tier
from repro.runtime.serving import (
    GenerationSession,
    build_executor,
)

V = 64


def _models():
    return [
        ("bilstm", BiLSTMSeq2Seq(RNNConfig(vocab_src=V, vocab_tgt=V, embed=32,
                                           hidden=32, layers=2,
                                           max_decode_len=20))),
        ("gru", GRUSeq2Seq(RNNConfig(vocab_src=V, vocab_tgt=V, embed=32,
                                     hidden=32, layers=1, max_decode_len=20))),
        ("marian", MarianTransformer(TransformerConfig(
            vocab_src=V, vocab_tgt=V, d_model=32, heads=4, d_ff=64,
            enc_layers=2, dec_layers=2, max_decode_len=20, max_src_len=64))),
    ]


def _ragged_batch(rng, lens, vocab=V):
    b, n = len(lens), max(lens)
    src = np.zeros((b, n), np.int32)
    mask = np.zeros((b, n), np.float32)
    for i, L in enumerate(lens):
        src[i, :L] = rng.integers(4, vocab, L)
        mask[i, :L] = 1.0
    return src, mask


# ------------------------------------------------ scan vs host, per model --
@pytest.mark.parametrize("name,model", _models())
@pytest.mark.parametrize("forced_len", [None, 9])
def test_batched_scan_matches_host_loop_bitwise(name, model, forced_len):
    """The acceptance invariant: the ONE-dispatch scan path must emit
    exactly the tokens the per-sequence host loop emits, row by row,
    including on ragged prefix-padded batches."""
    params = model.init(jax.random.PRNGKey(0))
    src, mask = _ragged_batch(np.random.default_rng(0), [5, 9, 3, 7])
    l_fast, t_fast = model.make_translate_batched(params)(
        src, mask, forced_len=forced_len)
    l_fast, t_fast = np.asarray(l_fast), np.asarray(t_fast)
    l_host, t_host = model.make_translate_batched(params, compiled=False)(
        src, mask, forced_len=forced_len)
    assert np.array_equal(l_fast, l_host)
    for i in range(src.shape[0]):
        m = int(l_fast[i])
        if forced_len is None:
            assert np.array_equal(t_fast[i, :m], t_host[i, :m])
            assert np.all(t_fast[i, m + 1:] == PAD_ID)   # post-EOS masked
        else:
            assert m == forced_len
            assert np.array_equal(t_fast[i, :forced_len],
                                  t_host[i, :forced_len])


class _Forced:
    """``model`` whose batched translate runs ``forced_len`` steps (the
    executor passes none) and records what the executor hands it."""

    def __init__(self, model, forced_len):
        self.model, self.forced_len, self.calls = model, forced_len, []

    def make_translate_batched(self, params):
        translate = self.model.make_translate_batched(params)

        def forced(src, src_mask=None):
            self.calls.append((np.shape(src), np.shape(src_mask)))
            return translate(src, src_mask, forced_len=self.forced_len)
        return forced


@pytest.mark.parametrize("name,model", _models())
@pytest.mark.parametrize("forced_len", [None, 9])
def test_nmt_executor_matches_mask_path_and_host_loop_bitwise(
        name, model, forced_len):
    """The executor sends the padded block and its row lengths (the
    program builds the mask): on ragged blocks in padded (batch, width)
    buckets its tokens and lengths are bit-identical to the explicit-mask
    call and to the per-sequence host loop."""
    params = model.init(jax.random.PRNGKey(4))
    lens = [5, 9, 3]                       # buckets to (4, 16)
    src, mask = _ragged_batch(np.random.default_rng(4), lens)
    wrapped = _Forced(model, forced_len)
    outs = build_executor(wrapped, kind="batched", params=params)(src, lens)
    assert wrapped.calls == [((4, 16), (4,))]   # the block, its lengths
    l_mask, t_mask = model.make_translate_batched(params)(
        src, mask, forced_len=forced_len)
    l_mask, t_mask = np.asarray(l_mask), np.asarray(t_mask)
    l_host, t_host = model.make_translate_batched(params, compiled=False)(
        src, mask, forced_len=forced_len)
    assert np.array_equal(l_mask, l_host)
    assert len(outs) == len(lens)
    for i, (m, toks) in enumerate(outs):
        assert m == int(l_mask[i]) == int(l_host[i])
        if forced_len is not None:
            assert m == forced_len
        assert np.array_equal(toks, t_mask[i, :max(m, 1)])
        assert np.array_equal(toks, t_host[i, :max(m, 1)])


def test_batched_translate_refuses_a_mask_with_holes():
    name, model = _models()[2]
    params = model.init(jax.random.PRNGKey(5))
    src, mask = _ragged_batch(np.random.default_rng(5), [4, 6])
    mask[1, 2] = 0.0
    for compiled in (True, False):
        with pytest.raises(ValueError, match="prefix mask"):
            model.make_translate_batched(params, compiled=compiled)(
                src, mask)


def test_batched_scan_matches_per_sequence_translate():
    """Row i of the batch == translate() of the trimmed row alone."""
    name, model = _models()[2]
    params = model.init(jax.random.PRNGKey(1))
    src, mask = _ragged_batch(np.random.default_rng(1), [4, 8])
    lens, toks = model.make_translate_batched(params)(src, mask)
    translate = model.make_translate(params)
    for i, L in enumerate([4, 8]):
        m, t = translate(src[i, :L])
        assert int(lens[i]) == m
        assert np.array_equal(np.asarray(toks)[i, :m], np.asarray(t))


# --------------------------------------------------- EOS masking semantics --
def test_batched_greedy_decode_eos_masking_controlled():
    """Deterministic fake decoder: row i emits tokens 10,11,... then EOS
    at its own stop step — lengths and PAD masking must be exact."""
    stops = jnp.asarray([2, 0, 5, 100], jnp.int32)   # 100 = never stops
    b = stops.shape[0]

    def fake_step(state, tok):
        i = state["i"]
        nxt = jnp.where(i >= stops, EOS_ID, 10 + i)
        logits = jax.nn.one_hot(nxt, V) * 10.0
        return {"i": i + 1}, logits

    lens, toks = batched_greedy_decode(fake_step,
                                       {"i": jnp.zeros((b,), jnp.int32)},
                                       b, max_len=8)
    lens, toks = np.asarray(lens), np.asarray(toks)
    assert lens.tolist() == [2, 0, 5, 8]
    assert toks[0].tolist() == [10, 11] + [PAD_ID] * 6
    assert np.all(toks[1] == PAD_ID)
    assert toks[3].tolist() == [10, 11, 12, 13, 14, 15, 16, 17]
    # forced_len ignores EOS entirely
    lens_f, toks_f = batched_greedy_decode(
        fake_step, {"i": jnp.zeros((b,), jnp.int32)}, b, max_len=8,
        forced_len=4)
    assert np.asarray(lens_f).tolist() == [4, 4, 4, 4]
    assert np.asarray(toks_f)[1].tolist() == [EOS_ID] * 4


# ------------------------------------------------------- pallas attention --
def test_marian_pallas_backend_matches_xla():
    cfg = TransformerConfig(vocab_src=V, vocab_tgt=V, d_model=32, heads=4,
                            d_ff=64, enc_layers=2, dec_layers=2,
                            max_decode_len=8, max_src_len=32)
    mx = MarianTransformer(cfg, attn_impl="xla")
    mp = MarianTransformer(cfg, attn_impl="pallas")
    params = mx.init(jax.random.PRNGKey(2))
    src, mask = _ragged_batch(np.random.default_rng(2), [5, 9])
    lx, tx = mx.make_translate_batched(params)(src, mask, forced_len=6)
    lp, tp = mp.make_translate_batched(params)(src, mask, forced_len=6)
    assert np.array_equal(np.asarray(tx), np.asarray(tp))
    assert np.array_equal(np.asarray(lx), np.asarray(lp))
    # teacher-forced (training) path parity, masked rows included
    tgt = np.random.default_rng(3).integers(4, V, (2, 5)).astype(np.int32)
    ox = mx.forward_teacher(params, src, mask, tgt)
    op = mp.forward_teacher(params, src, mask, tgt)
    np.testing.assert_allclose(np.asarray(ox), np.asarray(op),
                               rtol=2e-4, atol=2e-4)


def test_marian_attn_impl_validated():
    cfg = TransformerConfig(vocab_src=V, vocab_tgt=V)
    with pytest.raises(ValueError):
        MarianTransformer(cfg, attn_impl="cuda")


# --------------------------------------------------- GenerationSession -----
@pytest.fixture(scope="module")
def lm_session():
    cfg = smoke_config("qwen3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_session_scan_matches_host_loop(lm_session):
    cfg, model, params = lm_session
    scan = GenerationSession(model, params, max_len=32)
    host = GenerationSession(model, params, max_len=32, host_loop=True,
                             bucket_shapes=False)
    toks = np.random.default_rng(0).integers(
        4, cfg.vocab_size, (2, 8)).astype(np.int32)
    l1, o1 = scan.generate_with_lengths(toks, max_new=6)
    l2, o2 = host.generate_with_lengths(toks, max_new=6)
    assert np.array_equal(l1, l2)
    assert np.array_equal(o1, o2)
    out = scan.generate(toks, max_new=6)
    assert out.shape[0] == 2 and 1 <= out.shape[1] <= 6


def test_session_post_eos_positions_are_pad(lm_session):
    """Wherever a row contains EOS, everything after it must be PAD and
    the reported length must count only the pre-EOS tokens."""
    cfg, model, params = lm_session
    sess = GenerationSession(model, params, max_len=32)
    toks = np.random.default_rng(1).integers(
        4, cfg.vocab_size, (4, 6)).astype(np.int32)
    lens, out = sess.generate_with_lengths(toks, max_new=8)
    for i in range(out.shape[0]):
        row = out[i]
        eos = np.flatnonzero(row == EOS_ID)
        if eos.size:
            assert lens[i] == eos[0]
            assert np.all(row[eos[0] + 1:] == PAD_ID)
        else:
            assert lens[i] == np.sum(row != PAD_ID)


def test_session_ragged_prompt_matches_trimmed_solo(lm_session):
    cfg, model, params = lm_session
    sess = GenerationSession(model, params, max_len=32)
    rng = np.random.default_rng(2)
    full = rng.integers(4, cfg.vocab_size, (2, 9)).astype(np.int32)
    padded = full.copy()
    padded[1, 5:] = PAD_ID
    lens, out = sess.generate_with_lengths(padded, max_new=6,
                                           lengths=[9, 5])
    l_solo, o_solo = sess.generate_with_lengths(full[1:2, :5], max_new=6)
    assert lens[1] == l_solo[0]
    assert np.array_equal(out[1], o_solo[0])


def test_session_bucket_warns_once_per_shape(lm_session, caplog):
    cfg, model, params = lm_session
    sess = GenerationSession(model, params, max_len=32)
    toks = np.random.default_rng(3).integers(
        4, cfg.vocab_size, (3, 7)).astype(np.int32)
    with caplog.at_level(logging.WARNING, logger="repro.runtime.serving"):
        sess.generate_with_lengths(toks, max_new=4)
        n_first = sum("compiling new shape" in r.message
                      for r in caplog.records)
        sess.generate_with_lengths(toks[:, :5], max_new=4)  # same buckets
        n_second = sum("compiling new shape" in r.message
                       for r in caplog.records)
    assert n_first == 1 and n_second == 1
    # (3,7) and (3,5) both bucket to (4,8): one compiled shape
    assert sess._compiled_shapes == {(4, 8, 4)}


def test_session_capacity_and_ragged_guard(lm_session):
    cfg, model, params = lm_session
    sess = GenerationSession(model, params, max_len=16)
    toks = np.zeros((1, 12), np.int32)
    with pytest.raises(ValueError):
        sess.generate(toks, max_new=8)       # 12 + 8 > 16


# ---------------------------------------------------- batched executors ----
def test_batched_executor_matches_per_sequence_executor(lm_session):
    cfg, model, params = lm_session
    sess = GenerationSession(model, params, max_len=32)
    solo = build_executor(sess, kind="solo", max_new=6,
                          vocab_clip=cfg.vocab_size)
    batched = build_executor(sess, kind="batched", max_new=6,
                             vocab_clip=cfg.vocab_size)
    rng = np.random.default_rng(4)
    lens = [4, 7, 7, 5]
    block = np.full((4, 7), PAD_ID, np.int32)
    for i, L in enumerate(lens):
        block[i, :L] = rng.integers(4, cfg.vocab_size, L)
    outs = batched(block, lens)
    assert len(outs) == 4
    for i, L in enumerate(lens):
        m_b, t_b = outs[i]
        m_s, t_s = solo(block[i, :L])
        assert m_b == m_s
        assert np.array_equal(np.asarray(t_b), np.asarray(t_s))


def test_batched_executor_derives_lengths_from_trailing_pads(lm_session):
    cfg, model, params = lm_session
    sess = GenerationSession(model, params, max_len=32)
    batched = build_executor(sess, kind="batched", max_new=6,
                             vocab_clip=cfg.vocab_size)
    rng = np.random.default_rng(5)
    block = np.full((2, 8), PAD_ID, np.int32)
    block[0, :8] = rng.integers(4, cfg.vocab_size, 8)
    block[1, :3] = rng.integers(4, cfg.vocab_size, 3)
    auto = batched(block)
    explicit = batched(block, [8, 3])
    for (ma, ta), (me, te) in zip(auto, explicit):
        assert ma == me and np.array_equal(np.asarray(ta), np.asarray(te))


def test_batched_executor_recurrent_plan_runs_uniform_subgroups():
    """Plans with recurrent mixers (no ragged right-padding) must still
    serve ragged blocks — one uniform trimmed sub-batch per length —
    instead of raising."""
    cfg = smoke_config("rwkv6-3b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sess = GenerationSession(model, params, max_len=32)
    assert not sess.supports_ragged
    batched = build_executor(sess, kind="batched", max_new=4,
                             vocab_clip=cfg.vocab_size)
    solo = build_executor(sess, kind="solo", max_new=4,
                          vocab_clip=cfg.vocab_size)
    rng = np.random.default_rng(6)
    lens = [6, 3, 6]
    block = np.full((3, 6), PAD_ID, np.int32)
    for i, L in enumerate(lens):
        block[i, :L] = rng.integers(4, cfg.vocab_size, L)
    outs = batched(block, lens)
    for i, L in enumerate(lens):
        m_b, t_b = outs[i]
        m_s, t_s = solo(block[i, :L])
        assert m_b == m_s
        assert np.array_equal(np.asarray(t_b), np.asarray(t_s))


# --------------------------------------------------- engine submit_batch ---
def _flat_tier(beta, **kw):
    return Tier(DeviceProfile("t", LinearLatencyModel(0.0, 0.0, beta), 0.0),
                **kw)


def test_submit_batch_books_real_batches_into_occupancy():
    """4 concurrent requests on a batch_size=2 single-server tier: two
    real blocks; the second waits exactly the first's measured exec."""
    calls = []

    def bx(block, lens):
        calls.append(np.asarray(block).shape)
        return [(3, np.array([7, 7, EOS_ID]))] * len(lens)

    eng = CollaborativeEngine(
        tiers=[_flat_tier(0.1, name="t", servers=1, batch_size=2,
                          batched_executor=bx)],
        n2m=LinearN2M(1.0, 0.0), seed=0)
    reqs = [np.full((4,), 5, np.int32)] * 4
    res = eng.submit_batch(reqs, now_s=0.0)
    assert len(calls) == 2 and all(s[0] == 2 for s in calls)
    assert [r.m_out for r in res] == [3, 3, 3, 3]
    waits = sorted(r.wait_s for r in res)
    assert waits[0] == waits[1] == 0.0
    assert waits[2] == waits[3] > 0.0
    # the queued block's wait equals the first block's booked service
    first_service = min(r.latency_s for r in res)
    assert waits[2] == pytest.approx(first_service)


def test_submit_batch_without_batched_executor_falls_back_per_request():
    ran = []

    def solo(tokens):
        ran.append(len(tokens))
        return 2, np.array([9, EOS_ID])

    eng = CollaborativeEngine(
        tiers=[_flat_tier(0.1, name="t", servers=2, executor=solo)],
        n2m=LinearN2M(1.0, 0.0), seed=0)
    res = eng.submit_batch([np.full((3,), 5, np.int32),
                            np.full((6,), 5, np.int32)], now_s=0.0)
    assert ran == [3, 6]
    assert [r.m_out for r in res] == [2, 2]
    assert [r.n for r in res] == [3, 6]


def test_submit_batch_sheds_on_infeasible_deadline():
    """With the single server already booked (full, capacity 0) and a
    predicted execution far past the deadline, the whole slot is shed —
    and the batched executor is never invoked for it."""
    calls = []

    def bx(block, lens):
        calls.append(len(lens))
        time.sleep(0.002)                 # make the booked window real
        return [(1, np.array([5]))] * len(lens)

    eng = CollaborativeEngine(
        tiers=[_flat_tier(10.0, name="t", servers=1, queue_capacity=0,
                          batch_size=2, batched_executor=bx)],
        n2m=LinearN2M(1.0, 0.0), seed=0)
    eng.submit_batch([np.full((4,), 5, np.int32)], now_s=0.0)
    res = eng.submit_batch([np.full((4,), 5, np.int32)] * 2,
                           now_s=1e-4, deadline_s=0.5)
    assert calls == [1]                   # only the occupying request ran
    assert all(r.shed for r in res)
    assert eng.stats()["shed"] == 2


def test_submit_batch_respects_bounded_queue_capacity():
    """A concurrent slot must not oversubscribe a bounded queue: with one
    batch-slot free and queue_capacity=1, an 8-request slot on a lone
    tier keeps admitting (forced, counted as rejections) but the pending
    count is charged — mirroring what sequential submits enforce."""
    fast = _flat_tier(0.01, name="fast", servers=1, batch_size=2,
                      queue_capacity=1,
                      batched_executor=lambda b, l:
                      [(1, np.array([5]))] * len(l))
    slow = _flat_tier(5.0, name="slow", servers=1,
                      batched_executor=lambda b, l:
                      [(1, np.array([5]))] * len(l))
    eng = CollaborativeEngine(tiers=[fast, slow],
                              n2m=LinearN2M(1.0, 0.0), seed=0)
    res = eng.submit_batch([np.full((4,), 5, np.int32)] * 8, now_s=0.0)
    by_tier = {0: 0, 1: 0}
    for r in res:
        by_tier[r.device] += 1
    # 2 batch slots + 1 queue slot on the fast tier; the rest re-route
    assert by_tier[0] == 3
    assert by_tier[1] == 5


def test_submit_batch_partially_free_servers_not_overadmitted():
    """servers=2 with ONE busy and queue_capacity=0: a 2-request slot has
    exactly one free slot — the second member must re-route exactly as a
    sequential second submit would, not squat on the busy server."""
    def mk():
        fast = _flat_tier(0.1, name="fast", servers=2, queue_capacity=0,
                          batched_executor=lambda b, l:
                          [(1, np.array([5]))] * len(l))
        slow = _flat_tier(5.0, name="slow", servers=4,
                          batched_executor=lambda b, l:
                          [(1, np.array([5]))] * len(l))
        return CollaborativeEngine(tiers=[fast, slow],
                                   n2m=LinearN2M(1.0, 0.0), seed=0)

    seq = mk()
    seq.submit(np.full((4,), 5, np.int32), now_s=0.0)   # occupies server 1
    seq_routes = [seq.submit(np.full((4,), 5, np.int32), now_s=0.05).device
                  for _ in range(2)]

    par = mk()
    par.submit(np.full((4,), 5, np.int32), now_s=0.0)
    par_routes = [r.device for r in par.submit_batch(
        [np.full((4,), 5, np.int32)] * 2, now_s=0.05)]
    assert sorted(par_routes) == sorted(seq_routes) == [0, 1]


def test_submit_batch_preserves_request_order_and_ids():
    eng = CollaborativeEngine(
        tiers=[_flat_tier(0.01, name="t", servers=1, batch_size=4,
                          batched_executor=lambda b, l:
                          [(int(x), np.arange(int(x))) for x in l])],
        n2m=LinearN2M(1.0, 0.0), seed=0)
    lens = [6, 2, 9, 4]
    reqs = [np.full((L,), 5, np.int32) for L in lens]
    res = eng.submit_batch(reqs, now_s=0.0)
    # results in request order; m_out echoes each request's own length
    # (ids are assigned in drain order — length-sorted — but each result
    # lands at its request's position)
    assert [r.n for r in res] == lens
    assert [r.m_out for r in res] == lens
    assert sorted(r.req_id for r in res) == list(range(4))
