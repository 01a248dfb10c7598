"""Host seconds per window step from the step program's call to its
outputs being ready: the program's own ``hspmd.call`` span
(``runtime/telemetry.py``), the launch plus the wait on the device.
The window's steps are the program's last step records."""


def read(run):
    try:
        from repro.runtime.telemetry import recent_steps
    except ImportError:
        return None
    recs = list(recent_steps())
    if not run.steps or len(recs) < run.steps:
        return None
    vals = [r.spans.get("call") for r in recs[-run.steps:]]
    if None in vals:
        return None
    return sum(vals) / run.steps
