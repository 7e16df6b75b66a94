"""p95 of ``RequestResult.wait_s`` over the completed requests: arrival
to the start of service in the engine's virtual clock."""

from bench.harness import p95


def read(run):
    v = p95(run.records["wait_s"])
    return None if v is None else v * 1e3
