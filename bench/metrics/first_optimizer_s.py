"""Host seconds of the optimizer's first update on fresh state: the
program's own ``hspmd.optimizer`` span (``runtime/telemetry.py``) in
the newest step record whose update count is 1, a part of set-up's
first step."""


def read(run):
    try:
        from repro.runtime.telemetry import recent_steps
    except ImportError:
        return None
    first = [r for r in recent_steps() if r.updates == 1]
    if not first or "optimizer" not in first[-1].spans:
        return None
    return first[-1].spans["optimizer"]
