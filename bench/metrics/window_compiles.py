"""Backend compiles inside the window: the program's ``jit.compile``
records, each of which names the span it happened in."""

from bench import program_trace


def read(run):
    recs = program_trace.window_records(run)
    if recs is None:
        return None
    return sum(r.name == "jit.compile" for r in recs)
