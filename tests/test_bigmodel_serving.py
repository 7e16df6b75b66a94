"""Big-model tier serving: the unified model/executor API and the
sharded LM sessions.

Covers the PR's acceptance surface:

* ``LM(mixer_impl=...)`` parity — the "pallas" route (rwkv6 prefill via
  ``kernels/ops.rwkv6_wkv``, mamba2 via ``ops.ssd_scan``) agrees with
  the "xla" chunked math to float32 rounding, at the full-LM level (the
  raw-kernel parity lives in tests/test_kernels.py).
* Sharded-vs-unsharded decode parity — a smoke qwen3-8b / rwkv6-3b
  served through :func:`repro.runtime.sharded.make_sharded_session` on a
  forced 4-device host mesh emits token-identical output to the
  unsharded session, through both ``GenerationSession`` and
  ``ContinuousGenerationSession.serve`` (subprocess tests: the device
  count must be set before jax initializes).
* The unified API itself — ``models.registry.resolve``,
  ``build_executor`` kinds, and the ``DeprecationWarning`` contracts on
  every legacy entry point.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 4, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------- mixer_impl parity ----
@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-1.2b"])
def test_lm_mixer_impl_pallas_matches_xla(arch):
    """Full-LM prefill logits, carried state and the next decode step
    agree between mixer_impl='xla' and 'pallas' (rwkv6 + mamba2-hybrid
    plans).  The two chunk the scan differently and the kernel takes its
    prefix sums on the MXU, so they agree to float32 rounding, not bits."""
    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.models.model import LM

    cfg = smoke_config(arch)
    xla = LM(cfg, mixer_impl="xla")
    pal = LM(cfg, mixer_impl="pallas")
    params = xla.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = rng.integers(4, cfg.vocab_size, (2, 16)).astype(np.int32)
    tol = dict(rtol=1e-4, atol=1e-4)

    logits_x, st_x = xla.prefill(params, toks, max_len=24)
    logits_p, st_p = pal.prefill(params, toks, max_len=24)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_x),
                               **tol)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), **tol), st_p, st_x)

    nxt = jnp.argmax(logits_x, -1).astype(jnp.int32)[:, None]
    step_x, _ = xla.decode_step(params, st_x, nxt)
    step_p, _ = pal.decode_step(params, st_p, nxt)
    np.testing.assert_allclose(np.asarray(step_p), np.asarray(step_x), **tol)


def test_lm_mixer_impl_validated():
    from repro.configs import smoke_config
    from repro.models.model import LM

    with pytest.raises(ValueError, match="mixer_impl"):
        LM(smoke_config("rwkv6-3b"), mixer_impl="triton")


# --------------------------------------- sharded decode parity ----------
@pytest.mark.slow
@pytest.mark.parametrize("arch,layout", [("qwen3-8b", "auto"),
                                         ("qwen3-8b", "tp"),
                                         ("rwkv6-3b", "auto")])
def test_sharded_session_decode_is_bitwise_equal(arch, layout):
    """GenerationSession over a (2,2) host mesh == unsharded, token for
    token (ragged prompts via generate_with_lengths)."""
    out = _run(f"""
        import jax, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.configs import smoke_config
        from repro.models.model import LM
        from repro.runtime.serving import GenerationSession
        from repro.runtime.sharded import make_sharded_session

        cfg = smoke_config("{arch}")
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)
        toks = rng.integers(4, cfg.vocab_size, (4, 12)).astype(np.int32)
        lens = np.array([12, 7, 12, 9], np.int32)

        ref = GenerationSession(model, params, max_len=32)
        m_ref, out_ref = ref.generate_with_lengths(toks, max_new=8)

        mesh = make_host_mesh((2, 2))
        sess = make_sharded_session(model, params, mesh, max_len=32,
                                    batch_size=4, layout="{layout}")
        m_s, out_s = sess.generate_with_lengths(toks, max_new=8)
        assert np.array_equal(np.asarray(m_ref), np.asarray(m_s))
        assert np.array_equal(np.asarray(out_ref), np.asarray(out_s))
        print("layout", sess.layout, "equal True")
    """)
    assert "equal True" in out


@pytest.mark.slow
def test_sharded_continuous_session_matches_unsharded():
    """ContinuousGenerationSession.serve over the mesh == unsharded
    (slot-table in-flight batching on sharded params)."""
    out = _run("""
        import jax, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.configs import smoke_config
        from repro.models.model import LM
        from repro.runtime.serving import ContinuousGenerationSession
        from repro.runtime.sharded import make_sharded_session

        cfg = smoke_config("qwen3-8b")
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        prompts = [rng.integers(4, cfg.vocab_size,
                                int(rng.integers(4, 12))).astype(np.int32)
                   for _ in range(6)]

        ref = ContinuousGenerationSession(model, params, max_slots=4,
                                          max_len=32)
        got_ref = ref.serve(prompts, max_new=6)

        mesh = make_host_mesh((2, 2))
        sess = make_sharded_session(model, params, mesh, continuous=True,
                                    max_slots=4, max_len=32, batch_size=4)
        got = sess.serve(prompts, max_new=6)
        assert len(got) == len(got_ref)
        for (m_a, t_a), (m_b, t_b) in zip(got_ref, got):
            assert m_a == m_b
            assert np.array_equal(np.asarray(t_a), np.asarray(t_b))
        print("continuous equal True")
    """)
    assert "continuous equal True" in out


def test_init_sharded_places_each_shard_and_serves():
    """init_sharded draws the parameters straight into their TP shardings
    (values equal to model.init), and serve --mesh 1x4 runs on them."""
    out = _run("""
        import jax, numpy as np
        from repro.launch import serve
        from repro.launch.mesh import make_host_mesh
        from repro.configs import smoke_config
        from repro.models.model import LM
        from repro.runtime.sharded import init_sharded

        model = LM(smoke_config("qwen3-8b"))
        key = jax.random.PRNGKey(0)
        params, pol = init_sharded(model, key, make_host_mesh((1, 4)),
                                   batch_size=4, layout="tp")
        assert pol.model_axes == ("model",)
        split = 0
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(model.init(key))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
            split += a.sharding.shard_shape(a.shape) != a.shape
        assert split > 0
        toks = serve.main(["--arch", "qwen3-8b", "--smoke", "--mesh", "1x4",
                           "--requests", "4", "--max-new", "4"])
        assert np.asarray(toks).shape[0] == 4
        print("sharded init ok", split)
    """)
    assert "sharded init ok" in out


def test_serve_main_tiered_with_pallas_mixer(monkeypatch, tmp_path):
    """serve --tiered with mamba2 prefill through the SSD kernel (the big-
    model phase of chip_smoke.py, at smoke size): every request served."""
    from repro.launch import serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    stats = serve.main(["--arch", "zamba2-1.2b", "--smoke", "--tiered",
                        "--requests", "4", "--max-new", "4",
                        "--mixer-impl", "pallas"])
    assert stats["requests"] == 4 and stats["shed"] == 0


# ----------------------------------------------- unified registry -------
def test_registry_resolves_lm_and_cnmt_names():
    from repro.models.model import LM
    from repro.models.registry import available, resolve

    r = resolve("qwen3_8b")                 # underscore form normalizes
    assert r.family == "lm" and r.name == "qwen3-8b"
    assert isinstance(r.model, LM) and r.pair is None
    assert r.cfg.d_model == 256             # size="smoke" default

    r2 = resolve("cnmt:en-de", scale=0.1, vocab=128)
    assert r2.family == "nmt" and r2.pair == "de-en"
    assert r2.name == "cnmt:de-en"          # direction normalized

    names = available()
    assert "cnmt:de-en" in names and "qwen3-8b" in names

    with pytest.raises(KeyError, match="available"):
        resolve("not-a-model")
    with pytest.raises(ValueError, match="size"):
        resolve("qwen3-8b", size="huge")


def test_registry_threads_mixer_impl():
    from repro.models.registry import resolve

    assert resolve("rwkv6-3b", mixer_impl="pallas").model.mixer_impl == \
        "pallas"


def test_make_paper_model_shim_warns_and_delegates():
    from repro.nmt import GRUSeq2Seq
    from repro.nmt.registry import make_paper_model

    with pytest.warns(DeprecationWarning, match="make_paper_model"):
        model, pair = make_paper_model("fr-en", scale=0.1, vocab=128)
    assert isinstance(model, GRUSeq2Seq) and pair == "fr-en"


# ----------------------------------------------- unified executors ------
@pytest.fixture(scope="module")
def lm_session():
    import jax
    from repro.configs import smoke_config
    from repro.models.model import LM
    from repro.runtime.serving import GenerationSession

    cfg = smoke_config("qwen3-8b")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, GenerationSession(model, params, max_len=32)


def test_build_executor_solo_and_alias_agree(lm_session):
    from repro.runtime.serving import build_executor, make_tier_executor

    cfg, sess = lm_session
    new = build_executor(sess, kind="solo", max_new=4,
                         vocab_clip=cfg.vocab_size)
    with pytest.warns(DeprecationWarning, match="make_tier_executor"):
        old = make_tier_executor(sess, max_new=4, vocab_clip=cfg.vocab_size)
    toks = np.arange(4, 10, dtype=np.int32)
    m_n, t_n = new(toks)
    m_o, t_o = old(toks)
    assert m_n == m_o and np.array_equal(np.asarray(t_n), np.asarray(t_o))


def test_build_executor_batched_alias_warns(lm_session):
    from repro.runtime.serving import make_batched_tier_executor

    cfg, sess = lm_session
    with pytest.warns(DeprecationWarning, match="make_batched_tier_executor"):
        make_batched_tier_executor(sess, max_new=4)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_build_executor_batched_nmt(attn_impl):
    """kind='batched' with params= serves an NMT model (the chip_smoke
    paper phase at smoke size): each row of a ragged block matches that
    sentence translated alone, and a two-tier engine serves every
    request through real batched execution."""
    import jax
    from repro.core.latency_model import DeviceProfile, LinearLatencyModel
    from repro.core.length_regressor import LinearN2M
    from repro.data.tokenizer import PAD_ID
    from repro.models.registry import resolve
    from repro.runtime.engine import CollaborativeEngine, Tier
    from repro.runtime.serving import build_executor

    model = resolve("cnmt:en-zh", scale=0.1, vocab=128, max_decode_len=12,
                    attn_impl=attn_impl).model
    params = model.init(jax.random.PRNGKey(0))
    ex = build_executor(model, kind="batched", params=params)
    rng = np.random.default_rng(0)
    lens = [11, 6, 3]
    block = np.full((3, 11), PAD_ID, np.int32)
    for i, n in enumerate(lens):
        block[i, :n] = rng.integers(4, 128, n)
    translate = model.make_translate_batched(params)
    for (m, toks), row, n in zip(ex(block, lens), block, lens):
        m_ref, t_ref = translate(row[None, :n])
        assert m == int(m_ref[0])
        np.testing.assert_array_equal(toks, np.asarray(t_ref)[0, :max(m, 1)])

    eng = CollaborativeEngine(
        tiers=[Tier(DeviceProfile("e", LinearLatencyModel(1e-4, 2e-3, 5e-3)),
                    batched_executor=ex, batch_size=4, name="edge"),
               Tier(DeviceProfile("c", LinearLatencyModel(2e-5, 4e-4, 2e-3)),
                    batched_executor=ex, batch_size=4, name="cloud",
                    rtt_fn=lambda t: 0.03)],
        n2m=LinearN2M(0.8, 1.0))
    reqs = [rng.integers(4, 128, int(n)).astype(np.int32)
            for n in rng.integers(4, 40, 8)]
    res = eng.submit_batch(reqs[:4], now_s=0.0) + \
        eng.submit_batch(reqs[4:], now_s=1.0)
    assert len(res) == 8 and not any(r.shed for r in res)


def test_build_executor_raw_faults_and_errors():
    from repro.runtime.serving import TierFaultError, build_executor

    ex = build_executor(lambda t: (len(t), t), kind="raw", faults={0},
                        fault_message="boom")
    with pytest.raises(TierFaultError, match="boom"):
        ex(np.zeros(3, np.int32))
    assert ex(np.zeros(3, np.int32))[0] == 3
    assert ex.calls == {"n": 2, "faults": 1}

    with pytest.raises(ValueError, match="kind"):
        build_executor(lambda t: t, kind="bogus")
    with pytest.raises(ValueError, match="callable"):
        build_executor(object(), kind="raw")
    with pytest.raises(ValueError, match="params"):
        build_executor(object(), kind="split")
    with pytest.raises(ValueError, match="split"):
        build_executor(object(), kind="split", params={}, faults={0})


def test_make_faulty_executor_alias_warns():
    from repro.runtime.serving import make_faulty_executor

    with pytest.warns(DeprecationWarning, match="make_faulty_executor"):
        wrapped = make_faulty_executor(lambda t: (1, t), {0})
    assert wrapped.calls["n"] == 0


def test_build_executor_split_matches_deprecated_name():
    import jax
    from repro.models.registry import resolve
    from repro.runtime.serving import (build_executor,
                                       make_split_tier_executors)

    model = resolve("cnmt:fr-en", scale=0.1, vocab=128,
                    max_decode_len=24).model
    params = model.init(jax.random.PRNGKey(0))
    enc, dec = build_executor(model, kind="split", params=params)
    with pytest.warns(DeprecationWarning, match="make_split_tier_executors"):
        enc_o, dec_o = make_split_tier_executors(model, params)
    toks = np.arange(3, 9, dtype=np.int32)
    m_n, out_n = dec(enc(toks))
    m_o, out_o = dec_o(enc_o(toks))
    assert m_n == m_o and np.array_equal(np.asarray(out_n), np.asarray(out_o))


# -------------------------------------------- engine legacy kwargs ------
def test_engine_legacy_edge_cloud_kwargs_warn_but_work():
    """PR-1 constructor form still routes identically to tiers= — it just
    warns now."""
    import dataclasses

    from repro.core.latency_model import DeviceProfile, LinearLatencyModel
    from repro.core.length_regressor import LinearN2M
    from repro.runtime.engine import CollaborativeEngine, Tier

    edge = Tier(DeviceProfile("e", LinearLatencyModel(2e-3, 8e-3, 0.01), 0.0))
    cloud = Tier(DeviceProfile("c", LinearLatencyModel(4e-4, 1.6e-3, 2e-3),
                               0.0))
    rtt = lambda t: 0.05

    with pytest.warns(DeprecationWarning, match="tiers="):
        legacy = CollaborativeEngine(edge=edge, cloud=cloud,
                                     n2m=LinearN2M(1.0, 0.0), rtt_fn=rtt,
                                     seed=0)
    modern = CollaborativeEngine(
        tiers=[dataclasses.replace(edge, name="edge"),
               dataclasses.replace(cloud, name="cloud", rtt_fn=rtt)],
        n2m=LinearN2M(1.0, 0.0), seed=0)

    rng = np.random.default_rng(5)
    lens = rng.integers(2, 200, 40)
    for i, n in enumerate(lens):
        a = legacy.submit(np.zeros(int(n), np.int32), now_s=float(i))
        b = modern.submit(np.zeros(int(n), np.int32), now_s=float(i))
        assert a.device == b.device and a.latency_s == b.latency_s


def test_engine_tiers_form_does_not_warn():
    from repro.core.latency_model import DeviceProfile, LinearLatencyModel
    from repro.core.length_regressor import LinearN2M
    from repro.runtime.engine import CollaborativeEngine, Tier

    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        CollaborativeEngine(
            tiers=[Tier(DeviceProfile("e", LinearLatencyModel(1e-3, 1e-3,
                                                              1e-3), 0.0)),
                   Tier(DeviceProfile("c", LinearLatencyModel(1e-4, 1e-4,
                                                              1e-4), 0.0),
                        rtt_fn=lambda t: 0.05)],
            n2m=LinearN2M(1.0, 0.0), seed=0)
