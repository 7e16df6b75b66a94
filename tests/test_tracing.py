"""The in-process span recorder (``repro.tracing``) and its wiring into the
C-NMT serving path.

Covers: span nesting and parent ids, the trace id a request's spans
share, the bounded deque and its dropped count, ``enable(False)``,
compile records attributed to the span that compiled, the spans one
``CollaborativeEngine.submit_batch`` records over a modelled and a real
tier, that a decision runs on the host, and that the recorder changes no
result.
"""

import dataclasses
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core.latency_model import DeviceProfile, LinearLatencyModel
from repro.core.length_regressor import LinearN2M
from repro.nmt import MarianTransformer, TransformerConfig
from repro.runtime.engine import CollaborativeEngine, Tier
from repro.runtime.serving import build_executor


@pytest.fixture(autouse=True)
def fresh():
    tracing.reset()
    tracing.enable(True)
    yield
    tracing.enable(True)
    tracing.reset()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def test_spans_nest_with_parent_ids():
    with tracing.span("a") as a:
        with tracing.span("b", k=1):
            with tracing.span("c"):
                pass
        with tracing.span("d"):
            pass
    with tracing.span("e"):
        pass
    recs = {r.name: r for r in tracing.spans()}
    assert [r.name for r in tracing.spans()] == ["c", "b", "d", "a", "e"]
    assert recs["a"].id == a.id and recs["a"].parent is None
    assert recs["b"].parent == recs["a"].id
    assert recs["c"].parent == recs["b"].id
    assert recs["d"].parent == recs["a"].id
    assert recs["e"].parent is None
    assert recs["b"].attrs == {"k": 1}
    for r in recs.values():
        assert 0 <= r.dur_ns == r.t1_ns - r.t0_ns
    assert recs["a"].t0_ns <= recs["b"].t0_ns <= recs["c"].t1_ns \
        <= recs["b"].t1_ns <= recs["d"].t0_ns <= recs["a"].t1_ns
    # readers' filters
    assert [r.name for r in tracing.spans("b")] == ["b"]
    assert [r.name for r in tracing.spans(since_ns=recs["d"].t0_ns)] == \
        ["d", "e"]


def test_span_closes_on_exception_and_set_adds_attrs():
    with pytest.raises(ValueError):
        with tracing.span("outer") as sp:
            sp.set(w=8)
            raise ValueError("boom")
    with tracing.span("after"):
        pass
    recs = {r.name: r for r in tracing.spans()}
    assert recs["outer"].attrs == {"w": 8}
    assert recs["after"].parent is None


def test_trace_id_is_shared_by_a_requests_spans():
    with tracing.span("req", trace=7):
        with tracing.span("inner"):
            with tracing.span("leaf"):
                pass
        with tracing.bind(8):
            with tracing.span("other"):
                pass
        with tracing.span("again"):
            pass
    with tracing.span("loose"):
        pass
    traces = {r.name: r.attrs.get("trace") for r in tracing.spans()}
    assert traces == {"req": 7, "inner": 7, "leaf": 7, "other": 8,
                      "again": 7, "loose": None}


def test_deque_is_bounded_and_counts_dropped():
    extra = 5
    for i in range(tracing.CAPACITY + extra):
        with tracing.span("s", i=i):
            pass
    recs = tracing.spans()
    assert len(recs) == tracing.CAPACITY
    assert tracing.dropped() == extra
    # the oldest went first
    assert recs[0].attrs["i"] == extra
    assert recs[-1].attrs["i"] == tracing.CAPACITY + extra - 1
    tracing.reset()
    assert tracing.spans() == [] and tracing.dropped() == 0


def test_disabled_records_nothing():
    tracing.enable(False)
    with tracing.span("a", trace=1) as sp:
        sp.set(x=1)
        with tracing.bind(2):
            with tracing.span("b"):
                jax.jit(lambda x: x * 3.0 + time.time_ns() % 7)(np.ones(3))
    assert tracing.spans() == []
    tracing.enable(True)
    with tracing.span("c"):
        pass
    assert [r.name for r in tracing.spans()] == ["c"]


def test_compile_inside_a_span_is_attributed_to_it():
    salt = float(time.time_ns() % 1000003)    # a program never seen before

    @jax.jit
    def fresh(x):
        return x * 2.0 + salt

    x = np.ones((5,), np.float32)
    with tracing.span("outer"):
        with tracing.span("step", trace=3):
            fresh(x)
        fresh(x)                                # cached: no compile
    recs = _by_name(tracing.spans())
    compiles = recs[tracing.COMPILE]
    assert len(compiles) == 1
    (c,) = compiles
    assert c.parent == recs["step"][0].id
    assert c.t0_ns == c.t1_ns
    assert c.attrs["trace"] == 3 and c.attrs["seconds"] > 0


# --------------------------------------------------- the serving path ---
V = 48


@pytest.fixture(scope="module")
def marian_executor():
    model = MarianTransformer(TransformerConfig(
        vocab_src=V, vocab_tgt=V, d_model=32, heads=4, d_ff=64,
        enc_layers=1, dec_layers=1, max_decode_len=8, max_src_len=32))
    params = model.init(jax.random.PRNGKey(0))
    return build_executor(model, kind="batched", params=params)


def _engine(executor):
    """A local edge tier that runs the real translate and a modelled cloud
    tier behind a flat 10 ms link: short requests stay at the edge, long
    ones go to the cloud."""
    edge = Tier(DeviceProfile("edge", LinearLatencyModel(1e-3, 1e-3, 0.0),
                              0.0),
                name="edge", batched_executor=executor)
    cloud = Tier(DeviceProfile("cloud", LinearLatencyModel(1e-4, 1e-4, 0.0),
                               0.05),
                 name="cloud", rtt_fn=lambda t: 0.01)
    return CollaborativeEngine(tiers=[edge, cloud], n2m=LinearN2M(1.0, 0.0),
                               seed=3)


def _serve(executor, lengths):
    eng = _engine(executor)
    rng = np.random.default_rng(0)
    out = []
    for i, n in enumerate(lengths):
        toks = rng.integers(3, V, size=n).astype(np.int32)
        out += eng.submit_batch([toks], now_s=10.0 * i)
    return out


LENGTHS = [3, 30, 4, 25, 6]


def test_submit_batch_records_the_decision_and_translate_spans(
        marian_executor):
    _serve(marian_executor, LENGTHS[:1])          # compiles the edge bucket
    tracing.reset()
    res = _serve(marian_executor, LENGTHS)
    assert {r.tier_name for r in res} == {"edge", "cloud"}
    recs = tracing.spans()
    assert not [r for r in recs if r.name == tracing.COMPILE]
    by = _by_name(recs)
    ids = {r.id: r for r in recs}
    batches = by["engine.submit_batch"]
    decides = by["sched.decide"]
    assert len(batches) == len(decides) == len(LENGTHS)
    for i, (b, d) in enumerate(zip(batches, decides)):
        assert b.parent is None and b.attrs["trace"] == i
        assert d.parent == b.id and d.attrs["trace"] == i
        kids = [r for r in recs if r.parent == d.id]
        assert sorted(r.name for r in kids) == \
            ["sched.m_hat", "sched.t_exe", "sched.t_exe"]
        assert sorted(r.attrs["tier"] for r in kids
                      if r.name == "sched.t_exe") == [0, 1]
        assert all(r.attrs["trace"] == i for r in kids)
        assert all(r.attrs["host"] is True for r in kids)
    # one translate per request served at the edge, with its two children
    edge = [i for i, r in enumerate(res) if r.tier_name == "edge"]
    trans = by["exec.translate"]
    assert [ids[t.parent].attrs["trace"] for t in trans] == edge
    for t in trans:
        assert t.attrs["b"] == 1 and t.attrs["w"] >= 8
        kids = [r for r in recs if r.parent == t.id]
        assert [r.name for r in kids] == ["exec.dispatch", "exec.wait"]
        assert all(r.attrs["trace"] == t.attrs["trace"] for r in kids)
        assert t.t0_ns <= kids[0].t0_ns <= kids[1].t1_ns <= t.t1_ns


def test_translate_crosses_the_boundary_once_each_way(marian_executor):
    """Each translate uploads one host array (the block with its lengths
    folded in) in its jitted call and starts both outputs' copies to the
    host at dispatch; the spans nest as before."""
    _serve(marian_executor, LENGTHS[:1])
    tracing.reset()
    res = _serve(marian_executor, LENGTHS)
    recs = tracing.spans()
    by = _by_name(recs)
    ids = {r.id: r for r in recs}
    assert len(by["exec.translate"]) == \
        sum(r.tier_name == "edge" for r in res) > 0
    for name in ("exec.dispatch", "exec.wait"):
        assert len(by[name]) == len(by["exec.translate"])
        assert all(ids[r.parent].name == "exec.translate" for r in by[name])
    assert all(ids[t.parent].name == "engine.submit_batch"
               for t in by["exec.translate"])
    assert all(r.attrs["h2d"] == 1 for r in by["exec.dispatch"])
    assert all(r.attrs["prefetch"] is True for r in by["exec.wait"])


def test_executor_serves_numpy_outputs_without_prefetch():
    """A translate that hands back NumPy arrays (a wrapper that edits the
    tokens on the host, as the benchmark's fault test does) is served
    as is: nothing to copy, so ``prefetch`` is false."""
    model = MarianTransformer(TransformerConfig(
        vocab_src=V, vocab_tgt=V, d_model=32, heads=4, d_ff=64,
        enc_layers=1, dec_layers=1, max_decode_len=8, max_src_len=32))
    params = model.init(jax.random.PRNGKey(0))

    class OnHost:
        def make_translate_batched(self, p):
            translate = model.make_translate_batched(p)

            def host(src, src_mask=None, forced_len=None):
                lens, toks = translate(src, src_mask, forced_len=forced_len)
                return np.asarray(lens), np.asarray(toks)
            return host

    rng = np.random.default_rng(2)
    block = np.full((2, 9), 0, np.int32)
    block[0, :9] = rng.integers(3, V, 9)
    block[1, :4] = rng.integers(3, V, 4)
    want = build_executor(model, kind="batched", params=params)(block, [9, 4])
    tracing.reset()
    got = build_executor(OnHost(), kind="batched", params=params)(
        block, [9, 4])
    assert [m for m, _ in got] == [m for m, _ in want]
    for (_, a), (_, b) in zip(got, want):
        assert np.array_equal(a, b)
    (wait,) = tracing.spans("exec.wait")
    assert wait.attrs["prefetch"] is False


def test_decision_and_modelled_tier_create_no_device_array():
    """The regressor and the planes run on the host: a decision, and a
    request served by a modelled tier, neither put an array on a device
    nor read one back."""
    edge = Tier(DeviceProfile("edge", LinearLatencyModel(1e-3, 1e-3, 0.0),
                              0.05), name="edge")
    cloud = Tier(DeviceProfile("cloud", LinearLatencyModel(1e-4, 1e-4, 0.0),
                               0.05), name="cloud", rtt_fn=lambda t: 0.01)
    eng = CollaborativeEngine(tiers=[edge, cloud], n2m=LinearN2M(0.8, 1.0),
                              seed=3)
    toks = np.arange(3, 33, dtype=np.int32)
    with jax.transfer_guard("disallow"):
        d = eng.scheduler.decide(18, 0.0)
        res = eng.submit_batch([toks[:4], toks], now_s=1.0)
    assert d.m_hat == np.float32(0.8 * np.float32(18.0) + 1.0)
    assert [r.tier_name for r in res] == ["edge", "cloud"]
    kids = [r for r in tracing.spans()
            if r.name in ("sched.m_hat", "sched.t_exe")]
    assert len(kids) == 3 * 3
    assert all(r.attrs["host"] is True for r in kids)


def test_decide_fast_carries_no_span(marian_executor):
    eng = _engine(marian_executor)
    eng.scheduler.decide_fast(5.0, 5.0, 0.0)
    assert tracing.spans() == []


def test_results_identical_with_recorder_on_and_off(marian_executor):
    _serve(marian_executor, LENGTHS[:1])
    on = _serve(marian_executor, LENGTHS)
    tracing.enable(False)
    tracing.reset()
    off = _serve(marian_executor, LENGTHS)
    assert tracing.spans() == []
    assert len(on) == len(off)
    for a, b in zip(on, off):
        if a.tier_name == "edge":
            # the real tier's latency is its measured wall
            a = dataclasses.replace(a, latency_s=b.latency_s)
        assert a == b


def test_each_request_of_a_batch_has_its_own_trace_id(marian_executor):
    _serve(marian_executor, LENGTHS[:1])
    tracing.reset()
    eng = _engine(marian_executor)
    rng = np.random.default_rng(1)
    reqs = [rng.integers(3, V, size=n).astype(np.int32) for n in (3, 30, 4)]
    eng.submit_batch(reqs[:1], now_s=0.0)                  # trace 0
    res = eng.submit_batch(reqs, now_s=10.0)                # traces 1-3
    by = _by_name(tracing.spans())
    assert [b.attrs["trace"] for b in by["engine.submit_batch"]] == \
        [0, (1, 2, 3)]
    assert [d.attrs["trace"] for d in by["sched.decide"]] == [0, 1, 2, 3]
    # the edge serves requests 1 and 3 of the batch one block each
    assert [r.tier_name for r in res] == ["edge", "cloud", "edge"]
    assert [t.attrs["trace"] for t in by["exec.translate"]] == [0, 1, 3]
