"""Device seconds per window step in which an operation ran: the union
of the trace's operation intervals, mean over the chips."""


def read(run):
    if run.trace is None or not run.steps:
        return None
    return run.trace["busy_s"] / run.steps
