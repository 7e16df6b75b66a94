"""Model flops of all the window's served work (each completed request's
prompt and decoded tokens, counted from the sizes by
``bench/costs/<config>.py``) over the window's wall seconds times the
chip's bf16 peak."""

from bench.costs import load


def read(run):
    if run.peak is None:
        return None
    cost = load(run.config_name)
    flops = sum(cost.request_flops(run.cfg, n, m)
                for n, m in run.records["nm"])
    return 100.0 * flops / (run.window_s * run.peak["bf16_flops"])
