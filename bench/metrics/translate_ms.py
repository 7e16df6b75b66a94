"""Median host wall of one batched translate call (encoder, cache set-up
and the whole greedy decode in one compiled program; the executor
returns host arrays, so the call ends when the device is done)."""

import numpy as np


def read(run):
    t = run.records.get("translate_s", [])
    return float(np.median(t) * 1e3) if len(t) else None
