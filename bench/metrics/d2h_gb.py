"""GB the program fetched from the devices per window step: its own
``d2h_bytes`` counter (``runtime/telemetry.py``), the step program's
outputs.  The window's steps are the program's last step records."""


def read(run):
    try:
        from repro.runtime.telemetry import recent_steps
    except ImportError:
        return None
    recs = list(recent_steps())
    if not run.steps or len(recs) < run.steps:
        return None
    vals = [r.counts.get("d2h_bytes") for r in recs[-run.steps:]]
    if None in vals:
        return None
    return sum(vals) / run.steps / 1e9
