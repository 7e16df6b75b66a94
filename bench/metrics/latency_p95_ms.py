"""p95 over the window's completed requests of arrival to last token, from
``RequestResult.latency_s`` (the engine's virtual clock: queue wait, the
measured compute walls, and for the cloud tier the modelled link)."""

from bench.harness import p95


def read(run):
    v = p95(run.records["latency_s"])
    return None if v is None else v * 1e3
