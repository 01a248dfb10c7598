"""Model FLOP utilization, %: the window's training operations per
second (``bench/flops.py``) over the chips' bf16 peak
(``bench/peaks.json``)."""


def read(run):
    if run.peaks is None or run.window_s <= 0:
        return None
    rate = run.flops_per_step * run.steps / run.window_s
    return 100.0 * rate / (run.chips * run.peaks["bf16_flops_per_s"])
