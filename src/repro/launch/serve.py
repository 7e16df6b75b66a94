"""Serving driver: batched generation + optional C-NMT tiered routing.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --requests 16

Pass ``--mesh DxM`` (e.g. ``--mesh 2x2``) to serve the LM sharded over a
device mesh (``data`` x ``model`` axes); on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first so the host
platform exposes N devices.  Sharded parameters are initialised straight
into their shardings, so no device ever holds the whole model.
``--mixer-impl pallas`` routes rwkv6/mamba2 prefill through the Pallas
kernels.  The persistent compile cache is on (``launch/compile_cache``).

``main`` returns the engine's ``stats()`` with ``--tiered`` and the
generated token block otherwise.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.latency_model import DeviceProfile, LinearLatencyModel
from repro.core.length_regressor import LinearN2M
from repro.core.profiles import make_profile
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import resolve
from repro.runtime.engine import CollaborativeEngine, Tier
from repro.runtime.serving import GenerationSession, build_executor
from repro.runtime.sharded import init_sharded, make_sharded_session


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--tiered", action="store_true",
                    help="route through the C-NMT engine")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="shard the LM over a (data, model) host mesh, "
                         "e.g. 2x2 (needs that many visible devices)")
    ap.add_argument("--mixer-impl", default="xla", choices=("xla", "pallas"),
                    help="rwkv6/mamba2 prefill backend")
    args = ap.parse_args(argv)

    enable_compile_cache()
    r = resolve(args.arch, size="smoke" if args.smoke else "full",
                mixer_impl=args.mixer_impl)
    model, cfg = r.model, r.cfg
    key = jax.random.PRNGKey(0)
    if args.mesh:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        mesh = make_host_mesh((d, m))
        batch = min(args.requests, 8)
        params, _ = init_sharded(model, key, mesh, batch_size=batch)
        sess = make_sharded_session(model, params, mesh, max_len=64,
                                    batch_size=batch)
        print(f"[serve] sharded over {d}x{m} mesh, layout={sess.layout}")
    else:
        sess = GenerationSession(model, model.init(key), max_len=64)
    rng = np.random.default_rng(0)

    if not args.tiered:
        b = min(args.requests, 8)
        prompts = rng.integers(4, cfg.vocab_size, (b, 12)).astype(np.int32)
        t0 = time.perf_counter()
        out = sess.generate(prompts, max_new=args.max_new)
        print(f"[serve] generated {out.shape} in "
              f"{time.perf_counter()-t0:.2f}s (cold)")
        t0 = time.perf_counter()
        sess.generate(prompts, max_new=args.max_new)
        print(f"[serve] warm: {time.perf_counter()-t0:.3f}s")
        return out

    profile = make_profile("cp2", seed=0)
    edge_exec = build_executor(sess, kind="solo", max_new=args.max_new,
                               vocab_clip=cfg.vocab_size)
    edge_batched = build_executor(sess, kind="batched", max_new=args.max_new,
                                  vocab_clip=cfg.vocab_size)

    engine = CollaborativeEngine(
        tiers=[
            Tier(DeviceProfile("edge", LinearLatencyModel(1e-4, 2e-3, 5e-3)),
                 executor=edge_exec, batched_executor=edge_batched,
                 batch_size=4, name="edge"),
            Tier(DeviceProfile("pod", LinearLatencyModel(2e-5, 4e-4, 2e-3)),
                 name="cloud", rtt_fn=profile.rtt_at),
        ],
        n2m=LinearN2M(0.8, 1.0))
    # concurrent slots of 4: edge-routed members run as REAL batched
    # generates (submit_batch), not per-sequence calls
    slot = 4
    for i in range(0, args.requests, slot):
        reqs = [rng.integers(4, cfg.vocab_size,
                             (int(rng.integers(4, 48)),)).astype(np.int32)
                for _ in range(min(slot, args.requests - i))]
        engine.submit_batch(reqs, now_s=float(i))
    s = engine.stats()
    print(f"[serve] {s['requests']} reqs, mean {s['mean_latency_s']*1e3:.1f}ms,"
          f" offload {s['offload_frac']*100:.0f}%")
    return s


if __name__ == "__main__":
    main()
