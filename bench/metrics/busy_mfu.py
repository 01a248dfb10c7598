"""The step's training operations (``bench/flops.py``) over the time
the device was busy in the traced window, as a share of the chips' bf16
peak, %: how well the device uses the time it works.  Every kernel's
roofline share counts part of that busy time."""


def read(run):
    if run.peaks is None or run.trace is None or not run.steps:
        return None
    busy = run.trace["busy_s"]
    if busy <= 0:
        return None
    rate = run.flops_per_step * run.steps / busy
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])
