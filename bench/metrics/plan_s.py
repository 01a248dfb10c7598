"""Host seconds in planning: building the block program, the Session,
``compile_train`` and ``Session.load``."""


def read(run):
    return run.setup_spans.get("plan")
