"""Host seconds per window step in the optimizer
(``optim/adamw.py:sharded_apply_updates``)."""


def read(run):
    return run.step_spans.get("optimizer", 0.0) / run.steps \
        if run.steps else None
