"""Process start to window start: imports, weights, compiles, warm-up."""


def read(run):
    return run.setup_s
