"""Readers of bench/metrics/ on a hand-made run: the shares of the
peak by the host's window and by the device's busy time."""

import pytest
from tiny_cell import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import run

CELL = run.load_cell("qwen2-1.5b.b4s1024")


def _run(steps=16, window_s=51.8, busy_s=6.1):
    r = run.Run(cell=CELL, model=CELL["model"], traffic=CELL["traffic_mix"],
                chips=1, peaks=run.device_peaks("TPU v5 lite"))
    r.steps, r.window_s = steps, window_s
    if busy_s is not None:
        r.trace = {"busy_s": busy_s, "window_s": window_s}
    return r


def test_busy_mfu_is_the_step_work_over_busy_time():
    r = _run()
    # 4 layers at published widths: 2.56 GFLOP a token, 4096 tokens a
    # step, 16 steps in 6.1 busy seconds against 197 TFLOP/s
    flops = 6.0 * (4 * 46_792_704 + 233_373_696) + 4 * 6 * 1024 * 12 * 128
    want = 100.0 * flops * 4096 * 16 / 6.1 / 197e12
    assert run.read_metrics([("busy_mfu", "%")], r)["busy_mfu"]["value"] \
        == pytest.approx(want, rel=1e-12)
    got = run.read_metrics([("busy_mfu", "%"), ("mfu", "%")], r)
    assert got["busy_mfu"]["value"] * 6.1 == \
        pytest.approx(got["mfu"]["value"] * 51.8, rel=1e-12)


@pytest.mark.parametrize("busy_s", [None, 0.0])
def test_busy_mfu_is_silent_without_busy_time(busy_s):
    assert run.read_metrics([("busy_mfu", "%")], _run(busy_s=busy_s)) == {}
