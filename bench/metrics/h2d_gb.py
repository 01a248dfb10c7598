"""GB the program placed on the devices per window step: its own
``h2d_bytes`` counter (``runtime/telemetry.py``), the packed leaf
blocks handed to ``device_put``.  The window's steps are the program's
last step records."""


def read(run):
    try:
        from repro.runtime.telemetry import recent_steps
    except ImportError:
        return None
    recs = list(recent_steps())
    if not run.steps or len(recs) < run.steps:
        return None
    vals = [r.counts.get("h2d_bytes") for r in recs[-run.steps:]]
    if None in vals:
        return None
    return sum(vals) / run.steps / 1e9
