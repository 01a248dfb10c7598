"""The program's own step records (``repro.runtime.telemetry``) at CPU
size: counters exact from the shapes, program spans that contain the
harness's spans of the same calls, the spans in a profile, and the
readers of ``bench/metrics/`` that take them."""

import glob
import os

import numpy as np
import pytest
from tiny_cell import tiny_cell

from bench import run

SEED = 2**31 + 91
STEPS = 3
#: program spans, each against the harness span of the same calls
CONTAINS = [(("feed.pack", "feed.put"), "feed"), (("call",), "call"),
            (("fetch",), "fetch"), (("optimizer",), "optimizer")]
READERS = [("h2d_gb", "GB"), ("d2h_gb", "GB"), ("host_state_gb", "GB"),
           ("call_s", "s"), ("first_optimizer_s", "s")]


@pytest.fixture(scope="module")
def stepped(tmp_path_factory):
    """Set-up's first steps, then ``STEPS`` more under the profiler:
    the records and harness spans of those steps, the trace file, the
    readers' values and the sizes the counters should equal."""
    import jax
    from repro.runtime.telemetry import recent_steps

    cell = tiny_cell()
    spans = run.Spans()
    trainer = run.Trainer(cell, run.devices_for(1, require_tpu=False),
                          spans, log=lambda *_: None)
    tdir = str(tmp_path_factory.mktemp("trace"))
    harness = []
    with run.instrumented(spans, trainer.lw):
        trainer.start(SEED, log=lambda *_: None)
        first_step = spans.totals["first_step"]
        with jax.profiler.trace(tdir):
            for i in range(STEPS):
                before = dict(spans.totals)
                trainer.step(SEED, run.FIRST_STEPS + i)
                harness.append({n: v - before.get(n, 0.0)
                                for n, v in spans.totals.items()})
    records = list(recent_steps())[-STEPS:]
    r = run.Run(cell=cell, model=cell["model"], traffic=cell["traffic_mix"],
                chips=1, peaks=None, steps=STEPS)
    read = run.read_metrics(READERS, r)
    tr = cell["traffic_mix"]
    f = run.feeds(SEED, 0, tr["batch"], tr["seq"],
                  cell["model"]["vocab_size"])
    sizes = {"params": sum(int(np.prod(st.shape))
                           for st in trainer.sess.weights.values()),
             "feeds": sum(a.nbytes for a in f.values()),
             "loss": np.dtype(np.float32).itemsize * trainer.lw.n_mesh}
    trainer.release()
    trace = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    return {"records": records, "harness": harness, "trace": trace[0],
            "read": read, "sizes": sizes, "first_step": first_step}


def _expected(sizes):
    weights = 4 * sizes["params"]
    h2d = weights + sizes["feeds"]
    return {"h2d_bytes": h2d, "d2h_bytes": weights + sizes["loss"],
            "host_state_bytes": weights + h2d}


@pytest.mark.parametrize("name", ["h2d_bytes", "d2h_bytes",
                                  "host_state_bytes"])
def test_counters_are_exact_from_shapes(stepped, name):
    want = _expected(stepped["sizes"])[name]
    for rec in stepped["records"]:
        got = {**rec.counts, **rec.gauges}[name]
        assert got == want, (rec.step, got, want)


@pytest.mark.parametrize("program, harness", CONTAINS,
                         ids=[h for _, h in CONTAINS])
def test_program_spans_contain_the_harness_spans(stepped, program, harness):
    for rec, bench in zip(stepped["records"], stepped["harness"]):
        assert sum(rec.spans[p] for p in program) >= bench[harness]


def test_step_record_lies_inside_the_harness_step(stepped):
    for rec, bench in zip(stepped["records"], stepped["harness"]):
        assert 0 < sum(rec.spans.values()) <= rec.seconds <= bench["step"]
    steps = [rec.step for rec in stepped["records"]]
    assert steps == list(range(steps[0], steps[0] + STEPS))
    assert [rec.updates for rec in stepped["records"]] == \
        list(range(run.FIRST_STEPS + 1, run.FIRST_STEPS + STEPS + 1))


def test_spans_appear_in_the_profile_inside_bench_step(stepped):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(stepped["trace"])
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(("hspmd.", "bench.step"))]
    outer = [e for e in events if e[0] == "bench.step"]
    steps = [e for e in events if e[0] == "hspmd.train_step"]
    assert len(outer) == len(steps) == STEPS
    assert [e[3]["step_num"] for e in steps] == \
        [rec.step for rec in stepped["records"]]
    for name, s, e, _ in steps:
        assert any(s0 <= s and e <= e0 for _, s0, e0, _ in outer)
    inner = {e[0] for e in events} - {"bench.step", "hspmd.train_step"}
    assert inner == {"hspmd." + n for n in stepped["records"][0].spans}
    for name, s, e, _ in events:
        if name in inner:
            assert any(s0 <= s and e <= e0 for _, s0, e0, _ in steps)


def test_readers_read_the_window(stepped):
    recs, read = stepped["records"], stepped["read"]
    want = _expected(stepped["sizes"])
    assert read["h2d_gb"]["value"] == want["h2d_bytes"] / 1e9
    assert read["d2h_gb"]["value"] == want["d2h_bytes"] / 1e9
    assert read["host_state_gb"]["value"] == want["host_state_bytes"] / 1e9
    assert read["call_s"]["value"] == \
        pytest.approx(sum(r.spans["call"] for r in recs) / STEPS)
    assert 0 < read["first_optimizer_s"]["value"] <= stepped["first_step"]


@pytest.mark.parametrize("metric", READERS, ids=[n for n, _ in READERS])
def test_readers_are_silent_without_records(metric):
    from repro.runtime.telemetry import recent_steps

    cell = tiny_cell()
    r = run.Run(cell=cell, model=cell["model"], traffic=cell["traffic_mix"],
                chips=1, peaks=None, steps=STEPS)
    kept = list(recent_steps())
    recent_steps().clear()
    try:
        assert run.read_metrics([metric], r) == {}
    finally:
        recent_steps().extend(kept)
