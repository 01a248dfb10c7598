"""The reduction from a profiler trace to device numbers, on a small
trace recorded on one TPU v5e: the harness's window of 4 train steps
of a 1-layer block (d 256, 2/1 heads of 128, batch 2 x 128)."""

import os

import pytest
from tiny_cell import ROOT

from bench import trace

TRACE = os.path.join(ROOT, "tests", "bench", "data", "window.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(TRACE)


def test_window_busy_and_idle_add_up(summary):
    assert summary["devices"] == 1
    assert summary["window_s"] == pytest.approx(0.050829629, rel=1e-9)
    assert summary["busy_s"] == pytest.approx(0.000299587, rel=1e-6)
    idle = sum(summary["idle_by_host"].values())
    assert idle + summary["busy_s"] == pytest.approx(summary["window_s"],
                                                     rel=1e-9)


def test_ops_and_kernel_time(summary):
    # 96 operations a step, 4 steps, one flash-attention call per step
    assert sum(c for _, c in summary["ops"].values()) == 384
    secs, calls = trace.kernel_time(summary, "flash_attention")
    assert calls == 4
    assert secs == pytest.approx(1.4716e-05, rel=1e-6)
    assert trace.kernel_time(summary, "no_such_kernel") == (0.0, 0.0)


def test_idle_is_put_on_what_the_host_did(summary):
    idle = summary["idle_by_host"]
    assert set(idle) <= {"bench.step", "bench.feed", "bench.call",
                         "bench.fetch", "bench.optimizer", "host other"}
    assert max(idle, key=idle.get) == "bench.optimizer"
    assert idle["bench.feed"] > idle["bench.fetch"]


def test_breakdown_is_ranked_and_short(summary):
    b = trace.breakdown(summary)
    for key in ("device_ops", "idle_gaps"):
        secs = [s for _, s in b[key]]
        assert 1 <= len(secs) <= 10 and secs == sorted(secs, reverse=True)
    assert ["flash_attention", pytest.approx(1.4716e-05)] in b["device_ops"]


def test_op_names_and_kinds():
    assert trace.op_name("%fusion.12 = f32[4]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert trace.op_kind("convolution_add_fusion.3") == \
        "convolution_add_fusion"
    assert trace.op_kind("all-reduce-start.1.2") == "all-reduce-start"


def test_union_and_attribution_on_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    spans = [("bench.step", 0, 100), ("bench.fetch", 40, 60)]
    segments = trace._host_segments(spans, 0, 120)
    assert segments == [(0, 40, "bench.step"), (40, 60, "bench.fetch"),
                        (60, 100, "bench.step"), (100, 120, "host other")]
    idle = {"bench.step": 0.0, "bench.fetch": 0.0, "host other": 0.0}
    trace._attribute([(30, 50), (90, 110)], segments, idle, 1)
    assert idle == {"bench.step": 20.0, "bench.fetch": 10.0,
                    "host other": 10.0}
