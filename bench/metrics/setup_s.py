"""Process start to the window's start: imports, planning, weights,
compile (or the cache read) and the first steps."""


def read(run):
    return run.setup_s
