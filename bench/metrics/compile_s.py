"""Host seconds lowering the train step (``JaxExecutor.lowered``) and
compiling it ahead of time, or reading it from the compile cache."""


def read(run):
    return run.setup_spans.get("compile")
