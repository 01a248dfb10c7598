"""GB of host buffers the program keeps from step to step, the largest
over the window's steps: its own ``host_state_bytes`` gauge
(``runtime/telemetry.py``) over weights, optimizer state and pack
buffers, each buffer once.  The window's steps are the program's last
step records."""


def read(run):
    try:
        from repro.runtime.telemetry import recent_steps
    except ImportError:
        return None
    recs = list(recent_steps())
    if not run.steps or len(recs) < run.steps:
        return None
    vals = [r.gauges.get("host_state_bytes") for r in recs[-run.steps:]]
    if None in vals:
        return None
    return max(vals) / 1e9
