"""The harness end to end at CPU size: its refusal of a CPU platform,
a sound run's result line, and ``correct`` coming out false under each
fault planted beneath the timed path."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from tiny_cell import ROOT, tiny_cell

from bench import control, faults, run

ARGS = ["--workload", "qwen2-1.5b.b4s1024", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(run, "compile_cache", lambda: "off")


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_platform():
    out = _cli(ROOT)
    assert out.returncode == 2, out.stderr
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_gives_a_correct_result(trace):
    cell = tiny_cell()
    result, nums = run.run(cell, 2**31 + 77, 0.3, trace,
                           require_tpu=False, log=lambda *_: None)
    assert result["correct"] is True
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(cell["limits"])
    assert result["attempted"] >= 1 and result["failed"] == 0
    names = {n for n, _ in cell["per_layer" if trace else "end_to_end"]}
    assert set(result["metrics"]) <= names
    want = {"plan_s", "compile_s", "first_step_s", "optimizer_s"} \
        if trace else {"tokens_per_s", "setup_s"}
    assert want <= set(result["metrics"])
    json.dumps(result)


FAULTS = ["unchanged_state", "half_batch", "altered_token",
          "bfloat16_weights"]


@pytest.fixture(scope="module")
def readings():
    """A sound seed, then each fault, on one compiled step.
    ``bfloat16_weights`` stands in for the control here: XLA:CPU ignores
    the matmul precision that the control lowers on the chip."""
    cell = tiny_cell()
    saved = dict(faults.FAULTS)
    faults.FAULTS["bfloat16_weights"] = faults.bfloat16_weights
    cache = run.compile_cache
    run.compile_cache = lambda: "off"
    try:
        lines = control.readings(
            cell, [("sound", 21)] + [(k, 22 + i) for i, k in
                                     enumerate(FAULTS)],
            require_tpu=False)
    finally:
        faults.FAULTS.clear()
        faults.FAULTS.update(saved)
        run.compile_cache = cache
    return {line["kind"]: line for line in lines}


def test_sound_seed_reads_correct(readings):
    assert readings["sound"]["correct"] is True


@pytest.mark.parametrize("kind", FAULTS)
def test_each_fault_comes_out_not_correct(readings, kind):
    assert readings[kind]["correct"] is False
