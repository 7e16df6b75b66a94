"""Median over the window's translates (the program's ``exec.translate``
spans) of the host time outside their ``exec.wait`` child: padding the
block, the asynchronous dispatch, and slicing the rows out."""

import numpy as np

from bench import program_trace


def read(run):
    recs = program_trace.window_records(run)
    if recs is None:
        return None
    kids = program_trace.children(recs)
    t = [r.dur_ns - program_trace.child_ns(kids, r, ("exec.wait",))
         for r in recs if r.name == "exec.translate"]
    return float(np.median(t) * 1e-6) if t else None
