"""Mean host time per placement decision: the benchmark's span around
``engine.scheduler.decide``, all decision time over all decisions."""


def read(run):
    d = run.spans.durations("decide")
    return float(d.mean() * 1e6) if d.size else None
