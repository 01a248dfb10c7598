"""Host seconds per window step packing the leaves and placing them on
the devices (``LoweredGraph._pack``, ``_put_all``)."""


def read(run):
    return run.step_spans.get("feed", 0.0) / run.steps \
        if run.steps else None
