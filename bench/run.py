"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  Everything the cell needs is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json``, the driver of its
serving path in ``bench/serve/<driver>.py``, its plain reference in
``bench/refs/<config>.py`` and each metric in ``bench/metrics/<name>.py``.

Set-up (weights, compiles, warm-up) runs first and counts as ``setup_s``;
then the window runs for ``--seconds`` on the host clock (under
``--trace 1`` its last ``TRACED_S`` seconds are profiled); then the program's state is freed and the plain
reference checks a sample of what the window served.  The last line of
stdout is one JSON object; the numbers compared for ``correct`` close
both it and stderr.  Without a TPU, or with fewer chips than the cell
needs, the run exits non-zero and prints no result.

``--control 1`` puts the configuration's lower-precision control in the
program's place in the check (``check``): such a run has to print
``"correct": false``.  Benchmark runs leave it at 0.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# a traced run profiles the window's last seconds only: a whole window's
# device ops take minutes to write
TRACED_S = 5.0


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _metric_names(bench: dict, workload: str, trace: bool):
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if workload in m.get("workloads", [workload])]


def _fail(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
    sys.exit(3)


def check(ref, cfg: dict, sample, control: bool):
    """``correct`` and the numbers compared for it, each beside its limit.

    ``logit_gap`` is the widest gap of a served token below the
    reference's best.  With ``control`` the reference computed one
    precision step below the configuration's (``cfg["check"]["control"]``)
    takes the program's place: at the same prompts and served tokens,
    ``logit_gap`` is then the gap of the token that the lower precision
    puts first, and the program's own gap is reported beside it as
    ``program_logit_gap``."""
    from bench import harness

    prompts = [p for p, _ in sample]
    served = [s for _, s in sample]
    limit = cfg["check"]["limit"]
    gaps = {}
    if sample:
        hi = ref.logits(prompts, served)
        gaps["program"] = max(float(harness.logit_gaps(h, s).max())
                              for h, s in zip(hi, served))
        if control:
            lo = ref.logits(prompts, served,
                            precision=cfg["check"]["control"])
            gaps["control"] = max(float(harness.control_gaps(h, l).max())
                                  for h, l in zip(hi, lo))
    worst = gaps.get("control" if control else "program", float("inf"))
    checks = {"logit_gap": {"value": worst, "limit": limit},
              "checked_tokens": {"value": sum(len(s) for s in served),
                                 "limit": cfg["check"]["min_tokens"]}}
    if control and "program" in gaps:
        checks["program_logit_gap"] = {"value": gaps["program"],
                                       "limit": limit}
    correct = worst <= limit and \
        checks["checked_tokens"]["value"] >= cfg["check"]["min_tokens"]
    return correct, checks


def main(argv=None, *, require_tpu: bool = True, cfg_update=None,
         mix_update=None) -> dict:
    """One run.  The keywords are for the CPU rehearsal in the tests: it
    skips the look for a chip and shrinks the configuration and mix."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: the lower-precision control takes the "
                         "program's place in the check (never correct)")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the compile cache lives at one fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from bench import harness

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        _fail(f"unknown workload {args.workload!r}; have {sorted(cells)}")
    cell = cells[args.workload]
    cfg = {**harness.load_config(cell["config"]), **(cfg_update or {})}
    from bench import traffic
    mix = {**traffic.load(cell["traffic"]), **(mix_update or {})}

    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        _fail(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)")
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])

    spans = harness.Spans()
    watch = harness.CompileWatch()
    drv_mod = _load_module(HERE / "serve" / f"{cfg['driver']}.py",
                           f"bench_driver_{cfg['driver']}")
    drv = drv_mod.Driver(cfg, mix, args.seed, spans)
    drv.setup()
    # what set-up made lives for the whole run: frozen, full collections
    # in the window need not scan it again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    compiles_setup = watch.count
    spans.clear()

    tracing = bool(args.trace)
    trace_dir = TRACE_DIR / args.workload
    traced = {"ann": None, "at": None, "host": None}

    def mark(elapsed: float) -> None:
        """Start the profiler before the first request of the window's
        last TRACED_S seconds (all of it in a shorter window)."""
        if traced["at"] is not None or elapsed < args.seconds - TRACED_S:
            return
        from bench import trace as trace_mod
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_mod.profile_options())
        traced["ann"] = jax.profiler.TraceAnnotation("bench.window")
        traced["ann"].__enter__()
        traced["at"] = time.perf_counter()
        spans.tracing = True

    pauses = []

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([info["generation"], time.perf_counter()])
        else:
            pauses[-1][1] = time.perf_counter() - pauses[-1][1]

    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
    gc.callbacks.append(on_gc)
    with spans.span("window"):
        info = drv.window(args.seconds, mark if tracing else None)
    gc.callbacks.remove(on_gc)
    if traced["at"] is not None:
        traced["ann"].__exit__(None, None, None)
        spans.tracing = False
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"[bench] trace written in {time.perf_counter() - t_stop:.1f}"
              " s", file=sys.stderr, flush=True)
    compiles_window = watch.count - compiles_setup
    device = harness.device_info(cell["chips"])
    records = drv.records()
    print(f"[bench] window {info['wall_s']:.3f} s, {info['episodes']} "
          f"episode(s); attempted {records['attempted']}, completed "
          f"{records['attempted'] - records['failed']}; tiers "
          f"{records['tier_counts']}; compiles in window {compiles_window} "
          f"(set-up {compiles_setup}, {watch.seconds:.1f} s); host s in "
          + ", ".join(f"{k} {spans.durations(k).sum():.3f} (longest "
                      f"{spans.durations(k).max():.3f})"
                      for k in sorted(spans.intervals) if k != "window")
          + f"; {len(pauses)} collections ("
          + f"{sum(g == 2 for g, _ in pauses)} full), longest "
          + f"{max((d for _, d in pauses), default=0.0):.3f} s",
          file=sys.stderr, flush=True)

    summary = None
    if traced["at"] is not None and device["platform"] == "tpu":
        from bench import trace as trace_mod
        t_read = time.perf_counter()
        summary = trace_mod.reduce(trace_dir, chips=cell["chips"])
        print(f"[bench] trace read in {time.perf_counter() - t_read:.1f} s",
              file=sys.stderr, flush=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        # the traced window on the host clock, to pick the calls made in it
        shift = summary.annotated[0] - traced["at"]
        traced["host"] = (summary.window[0] - shift,
                          summary.window[1] - shift)
    from bench import peaks
    run = Run(records=records, window_s=info["wall_s"], setup_s=setup_s,
              spans=spans, trace=summary, traced_window=traced["host"],
              cfg=cfg, mix=mix,
              peak=(peaks.lookup(device["kind"]) if device["platform"] == "tpu"
                    else None), calls=drv, config_name=cell["config"])
    metrics = {}
    for name, unit in _metric_names(bench, args.workload, tracing):
        reader = _load_module(HERE / "metrics" / f"{name}.py",
                              f"bench_metric_{name.replace('.', '_')}")
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}

    # the check: the program's state goes first, the reference runs alone
    sample = drv.check_sample(cfg["check"]["sample"], args.seed)
    spec = drv.spec
    drv.free()
    del run
    gc.collect()
    ref_mod = _load_module(HERE / "refs" / f"{cell['config']}.py",
                           "bench_ref")
    ref = ref_mod.Reference(cfg, spec, args.seed)
    correct, checks = check(ref, cfg, sample, bool(args.control))

    result = {"correct": correct, "attempted": records["attempted"],
              "failed": records["failed"], "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"[bench] check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
