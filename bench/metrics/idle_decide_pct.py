"""Share of the traced window in which the device was idle while the
host was in a placement decision: idle gaps whose middle lies in a
``sched.decide`` span of the program, on the bracketed clock
(``bench/program_trace.py``), over the window's length."""

from bench import program_trace


def read(run):
    al = program_trace.aligned(run)
    if al is None or run.trace.window_s <= 0:
        return None
    return 100.0 * al.idle_within("sched.decide") / run.trace.window_s
