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


def _traced(calls=176.0, secs=0.4966):
    r = _run()
    r.trace["ops"] = {"flash_attention.1": (secs / 2, calls / 2),
                      "flash_attention.2": (secs / 2, calls / 2),
                      "fusion.3": (9.8, 1000.0)}
    return r


def test_flash_attn_roofline_of_a_dense_step():
    """Every call of the dense step is the same: its least time, by the
    calls in the trace, over their device time."""
    from bench import flops

    r = _traced()
    ops, nbytes = flops.flash_attention(4, 12, 2, 1024, 128)
    least = max(ops / 197e12, nbytes / 819e9)
    got = run.read_metrics([("flash_attn_roofline", "%")], r)
    assert got["flash_attn_roofline"]["value"] == \
        100.0 * least * 176.0 / 0.4966


class _Windowed:
    """An architecture whose step makes one full and three windowed
    calls."""

    @staticmethod
    def attention_calls(m, traffic, layout):
        from bench import flops

        full = flops.flash_attention(1, 32, 4, 4096, 128)
        near = flops.flash_attention(1, 32, 4, 4096, 128, window=1024)
        return [near, near, near, full]


def test_flash_attn_roofline_takes_the_mean_call():
    from bench import flops

    r = _traced(calls=8.0, secs=0.01)
    r.cell = dict(CELL, arch=_Windowed)
    peak, bw = 197e12, 819e9
    times = [max(ops / peak, nbytes / bw) for ops, nbytes in
             _Windowed.attention_calls(None, None, None)]
    got = run.read_metrics([("flash_attn_roofline", "%")], r)
    assert got["flash_attn_roofline"]["value"] == \
        pytest.approx(100.0 * sum(times) / 4 * 8.0 / 0.01, rel=1e-12)
    # counted at full causal pairs, the windowed calls would read 2.29x
    full = flops.flash_attention(1, 32, 4, 4096, 128)[0] / peak
    assert full / times[0] == pytest.approx(2.2859, abs=1e-4)


def test_flash_attn_roofline_is_silent_without_kernel_calls():
    r = _traced()
    r.trace["ops"] = {"fusion.3": (9.8, 1000.0)}
    assert run.read_metrics([("flash_attn_roofline", "%")], r) == {}
