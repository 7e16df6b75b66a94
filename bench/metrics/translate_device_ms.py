"""Mean device time of one translate program run (``jit_nmt_translate``
on the XLA Modules line) in the traced window."""

from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    runs = program_trace.translate_modules(run.trace)
    return sum(m.dur for m in runs) / len(runs) * 1e3 if runs else None
