"""Output tokens of the window's completed requests over the window's wall
seconds on the host clock (both tiers share the chip one after the
other, so this is the chip's output rate, host work included)."""


def read(run):
    return run.records["tokens_out"] / run.window_s
