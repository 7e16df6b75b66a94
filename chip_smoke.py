"""Bring-up check: the C-NMT serving path on a TPU, at published widths.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded path, 4-chip host

One chip, one process, two phases:

1. paper path -- a ``CollaborativeEngine`` with an edge and a cloud tier,
   both serving the paper's en-zh Marian (d_model 512, 8 heads, d_ff
   2048, 6+6 layers, the 65001-token vocabulary of Helsinki-NLP
   opus-mt-en-zh) through real batched executors with Pallas attention.
   The cloud link replays the ``cp2`` RTT profile.  16 requests of 4-64
   tokens go in through ``submit_batch``; every one must be served.  On
   one batch the Pallas logits are compared with the XLA ones.
2. big-model tier -- ``repro.launch.serve`` for zamba2-1.2b at published
   widths (float32), tiered, mamba2 prefill through the Pallas SSD
   kernel; then one prefill is compared between the Pallas and the XLA
   mixer.

``--four-chips`` runs only the sharded path: qwen3-8b at published
widths, tensor-parallel over a 1x4 mesh through ``serve --mesh 1x4``,
and a 2-layer full-width qwen3-8b served sharded and unsharded.

Weights are random, drawn from ``--seed``.  Every step that should run
a Pallas kernel is checked for ``tpu_custom_call`` in its compiled
text.  The wall, compile and memory figures printed per phase are
bring-up observations, not benchmark numbers.  The last line of stdout
is one JSON object, printed only when every phase passed; without a TPU
the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

MARIAN_VOCAB = 65001       # Helsinki-NLP/opus-mt-en-zh
N_REQUESTS = 16
# Pallas vs XLA (and sharded vs unsharded) logits: the largest absolute
# difference over the largest absolute reference logit.  Only the
# attention/scan core (or the collective order) differs between the two
# sides, so a correct kernel lands far below this; a wrong mask, chunk
# carry or shard lands at O(1).  Pallas vs XLA runs both sides at
# "highest" matmul precision: at the default, XLA takes float32 matmuls
# in one bfloat16 pass, and over zamba2's 38 random-weight layers that
# drift alone exceeds the tolerance.
REL_TOL = 2e-2


def _fail(msg: str, code: int = 1) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(code)


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def _check_close(name: str, got, want, tol: float = REL_TOL) -> float:
    err = _rel_err(got, want)
    _log(f"  {name}: rel err {err:.3e} (tol {tol:.0e})")
    if not err <= tol:
        raise AssertionError(f"{name}: rel err {err} > {tol}")
    return err


def _peak_bytes() -> list:
    """``peak_bytes_in_use`` of every device, as the runtime reports it."""
    import jax
    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def _compile_with_kernel(name: str, fn, *args):
    """Compile ``fn`` for these args; require a Pallas kernel in it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in compiled text")
    _log(f"  {name}: tpu_custom_call present")
    return compiled


class _Phase:
    """Per-phase wall time, backend compile time and peak device bytes."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def run(self, name: str, fn):
        _log(f"phase {name}: start")
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        _log(f"phase {name}: ok, wall {wall:.1f}s, backend compile "
             f"{self.compile_s - c0:.1f}s, peak_bytes_in_use {_peak_bytes()}")
        return out


# --------------------------------------------------------- paper path --
def paper_phase(seed: int) -> None:
    import collections

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.latency_model import DeviceProfile, LinearLatencyModel
    from repro.core.length_regressor import LinearN2M
    from repro.core.profiles import make_profile
    from repro.data.tokenizer import BOS_ID
    from repro.models.registry import resolve
    from repro.runtime.engine import CollaborativeEngine, Tier
    from repro.runtime.serving import build_executor

    def marian(attn_impl):
        return resolve("cnmt:en-zh", scale=1.0, vocab=MARIAN_VOCAB,
                       attn_impl=attn_impl).model

    model = marian("pallas")
    c = model.cfg
    widths = (c.d_model, c.heads, c.d_ff, c.enc_layers, c.dec_layers)
    if widths != (512, 8, 2048, 6, 6):
        raise AssertionError(f"en-zh Marian widths {widths}")
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))

    # the paper runs the same model at the edge and in the cloud: one
    # executor (one set of compiled buckets) serves both tiers
    execute = build_executor(model, kind="batched", params=params)
    link = make_profile("cp2", seed=seed)
    engine = CollaborativeEngine(
        tiers=[Tier(DeviceProfile("edge", LinearLatencyModel(1e-4, 2e-3,
                                                             5e-3)),
                    batched_executor=execute, batch_size=4, name="edge"),
               Tier(DeviceProfile("cloud", LinearLatencyModel(2e-5, 4e-4,
                                                              2e-3)),
                    batched_executor=execute, batch_size=4, name="cloud",
                    rtt_fn=link.rtt_at)],
        n2m=LinearN2M(0.8, 1.0))
    rng = np.random.default_rng(seed)
    reqs = [rng.integers(4, MARIAN_VOCAB, (int(rng.integers(4, 65)),))
            .astype(np.int32) for _ in range(N_REQUESTS)]
    results = []
    for i in range(0, N_REQUESTS, 4):
        results += engine.submit_batch(reqs[i:i + 4], now_s=float(i))
    served = [r for r in results if not r.shed]
    if len(served) != N_REQUESTS or any(r.m_out < 0 for r in served):
        raise AssertionError(f"served {len(served)} of {N_REQUESTS}")
    mix = collections.Counter(r.tier_name for r in served)
    s = engine.stats()
    _log(f"  served {len(served)}/{N_REQUESTS}, shed 0, placement "
         f"{dict(mix)}, mean latency {s['mean_latency_s'] * 1e3:.1f}ms "
         f"(includes first-call compiles)")

    src = jnp.asarray(rng.integers(4, MARIAN_VOCAB, (4, 32)), jnp.int32)
    mask = (jnp.arange(32)[None, :]
            < jnp.asarray([32, 20, 9, 4])[:, None]).astype(jnp.float32)
    tgt = jnp.asarray(rng.integers(4, MARIAN_VOCAB, (4, 16)), jnp.int32)
    _compile_with_kernel("served translate", execute.translate.jitted,
                         params, src, mask)

    def two_steps(m):
        def f(p, src, mask):
            enc, mm = m.encode(p, src, mask)
            st = m.init_cache(p, enc, mm)
            st, lg = m.decode_step(p, st, jnp.full((4,), BOS_ID, jnp.int32))
            _, lg = m.decode_step(p, st, jnp.argmax(lg, -1).astype(jnp.int32))
            return lg
        return f

    xla = marian("xla")
    with jax.default_matmul_precision("highest"):
        teach_p = _compile_with_kernel("teacher-forced forward",
                                       model.forward_teacher, params, src,
                                       mask, tgt)(params, src, mask, tgt)
        teach_x = jax.jit(xla.forward_teacher)(params, src, mask, tgt)
        step_p = _compile_with_kernel("decode step", two_steps(model),
                                      params, src, mask)(params, src, mask)
        step_x = jax.jit(two_steps(xla))(params, src, mask)
    _check_close("marian teacher-forced logits, pallas vs xla",
                 teach_p, teach_x)
    _check_close("marian decode-step logits, pallas vs xla", step_p, step_x)


# ----------------------------------------------------- big-model tier --
def zamba_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models.registry import resolve

    stats = serve.main(["--arch", "zamba2-1.2b", "--tiered",
                        "--requests", "4", "--max-new", "8",
                        "--mixer-impl", "pallas"])
    if stats["requests"] != 4 or stats["shed"]:
        raise AssertionError(f"zamba2 serve: {stats}")

    pal = resolve("zamba2-1.2b", size="full", mixer_impl="pallas").model
    xla = resolve("zamba2-1.2b", size="full", mixer_impl="xla").model
    if (pal.cfg.d_model, pal.cfg.ssm.state_dim) != (2048, 64):
        raise AssertionError("zamba2-1.2b is not at published widths")
    params = jax.jit(pal.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    # 192 tokens: two 128-token kernel chunks, the second padded
    toks = jnp.asarray(rng.integers(4, pal.cfg.vocab_size, (2, 192)),
                       jnp.int32)

    def prefill(m):
        return lambda p, t: m.prefill(p, t, max_len=200)[0]

    with jax.default_matmul_precision("highest"):
        got = _compile_with_kernel("zamba2 prefill (pallas mixer)",
                                   prefill(pal), params, toks)(params, toks)
        want = jax.jit(prefill(xla))(params, toks)
    _check_close("zamba2 prefill logits, pallas vs xla", got, want)


# ------------------------------------------------------ sharded path ---
def sharded_phase(seed: int) -> None:
    import dataclasses
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.models.config import LayerGroup
    from repro.models.model import LM
    from repro.runtime.serving import GenerationSession
    from repro.runtime.sharded import make_sharded_session

    cfg = get_config("qwen3-8b")
    model_bytes = sum(x.size * x.dtype.itemsize for x in
                      jax.tree.leaves(LM(cfg).params_spec()))
    out = serve.main(["--arch", "qwen3-8b", "--mesh", "1x4",
                      "--requests", "8", "--max-new", "8"])
    out = np.asarray(out)
    gc.collect()     # the served model's shards go before the cut's load
    if out.shape[0] != 8 or out.min() < 0 or out.max() >= cfg.vocab_size:
        raise AssertionError(f"qwen3-8b sharded serve gave {out.shape}")
    peaks = _peak_bytes()
    _log(f"  qwen3-8b TP=4: model {model_bytes / 2**30:.2f} GiB, "
         f"peak_bytes_in_use per device "
         f"{[round(p / 2**30, 2) for p in peaks]} GiB")
    if max(peaks) >= model_bytes:
        raise AssertionError("a device held the whole model")

    # depth cut at full widths: fits one chip unsharded, so it is the
    # reference for the tensor-parallel layout
    cut = dataclasses.replace(
        cfg, name="qwen3-8b-2layer",
        layer_plan=(LayerGroup(mixer="attn", ffn="dense", count=2),))
    model = LM(cut)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, cut.vocab_size, (4, 16)).astype(np.int32)
    prefill = jax.jit(lambda p, t: model.prefill(p, t)[0])

    ref = GenerationSession(model, params, max_len=32)
    _, out_ref = ref.generate_with_lengths(toks, max_new=8)
    logits_ref = prefill(params, jnp.asarray(toks))
    sess = make_sharded_session(model, params, make_host_mesh((1, 4)),
                                max_len=32, batch_size=4, layout="tp")
    _, out_s = sess.generate_with_lengths(toks, max_new=8)
    logits_s = prefill(sess.params, jnp.asarray(toks))
    same = bool(np.array_equal(out_ref, out_s))
    _log(f"  2-layer qwen3-8b layout {sess.layout}: greedy tokens "
         f"{'equal' if same else 'differ'}")
    if not same:
        _check_close("2-layer qwen3-8b prefill logits, 1x4 vs 1 chip",
                     logits_s, logits_ref)
    else:
        _log(f"  prefill logits rel err "
             f"{_rel_err(logits_s, logits_ref):.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "__init__.py").is_file():
        _fail(f"{SRC / 'repro'} not found: run chip_smoke.py from a "
              f"checkout of the repository", code=2)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        _fail(f"no TPU: JAX's first device is {platform!r}; this check "
              f"runs only on a TPU and has no CPU fallback")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        _fail(f"need {want} TPU chips, JAX sees {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache
    _log(f"device {devices[0].device_kind} x{len(devices)}, "
         f"jax {jax.__version__}, compile cache {enable_compile_cache()}")

    phases = _Phase()
    if args.four_chips:
        phases.run("sharded qwen3-8b", lambda: sharded_phase(args.seed))
    else:
        phases.run("paper en-zh marian", lambda: paper_phase(args.seed))
        phases.run("zamba2-1.2b tier", lambda: zamba_phase(args.seed))

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
