"""Host wall seconds of the first train step, whose extra over a later
step is the host optimizer's and packer's first-touch work."""


def read(run):
    return run.setup_spans.get("first_step")
