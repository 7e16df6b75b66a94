"""Roofline share of the ``flash_decode`` kernel over the traced window:
the least time the chip could take for every call of the translates that
began in it (the larger of its operations over the bf16 peak and its
bytes over HBM bandwidth, from ``bench/costs/flash_decode.py`` and the
call shapes the driver recorded) over the kernel's summed device time in
the trace."""

from bench.costs import load


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.kernel_seconds(
        run.calls.kernel_patterns["flash_decode"])
    if t <= 0:
        return None
    cost = load("flash_decode")
    least = 0.0
    for args in run.calls.kernel_calls("flash_decode",
                                       between=run.traced_window):
        flops, nbytes = cost.cost(*args)
        least += max(flops / run.peak["bf16_flops"],
                     nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
