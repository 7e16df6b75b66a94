"""Find a cell's knee once: serve its mix at several mean rates, one
process, one set-up, and print the backlog at each.

    python3 bench/sweep.py --workload <cell> --seed <n> --rates 4,8,12 \
        [--seconds 5]

For each rate a fresh engine serves the mix for ``--seconds`` of host
time.  ``end_wait_ms`` is the
mean queue wait of the last tenth of the arrivals against the first
tenth: a backlog that grows through the window shows as a rising end
wait.  The cell's rate is then fixed at about four fifths of the highest
rate whose end wait stays flat.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import numpy as np
    from bench import harness, run, traffic
    import jax

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = harness.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    spans = harness.Spans()
    mod = run._load_module(ROOT / "bench" / "serve" / f"{cfg['driver']}.py",
                           "bench_sweep_driver")
    drv = mod.Driver(cfg, mix, args.seed, spans)
    drv.setup()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix["arrivals"]["rate_hz"] = rate
        drv.engine = drv._engine()
        spans.clear()
        info = drv.window(args.seconds)
        rec = drv.records()
        wait = np.asarray(rec["wait_s"])
        k = max(len(wait) // 10, 1)
        out = {"rate_hz": rate, "wall_s": info["wall_s"],
               "attempted": rec["attempted"], "failed": rec["failed"],
               "first_wait_ms": float(wait[:k].mean() * 1e3),
               "end_wait_ms": float(wait[-k:].mean() * 1e3),
               "latency_p95_ms": harness.p95(rec["latency_s"]) * 1e3,
               "tokens_per_s": rec["tokens_out"] / info["wall_s"],
               "tiers": rec["tier_counts"]}
        if rec.get("translate_s"):
            out["translate_ms_median"] = float(
                np.median(rec["translate_s"]) * 1e3)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
