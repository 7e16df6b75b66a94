"""Mean over the window's placement decisions (the program's
``sched.decide`` spans) of the time in their ``sched.m_hat`` and
``sched.t_exe`` children: the N->M regressor and each tier's plane,
evaluated as device scalars and read back to the host."""

import numpy as np

from bench import program_trace


def read(run):
    recs = program_trace.window_records(run)
    if recs is None:
        return None
    kids = program_trace.children(recs)
    t = [program_trace.child_ns(kids, r, ("sched.m_hat", "sched.t_exe"))
         for r in recs if r.name == "sched.decide"]
    return float(np.mean(t) * 1e-3) if t else None
