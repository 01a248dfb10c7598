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


def test_a_new_architecture_needs_no_edit(tmp_path):
    """A new architecture is a module, a configuration naming it and a
    cell: a root holding a renamed copy of the dense module and only
    new files beside the benchmark's own runs a tiny cell correct."""
    spec = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    (cell,) = [w for w in spec["workloads"]
               if w["name"] == "qwen2-1.5b.b4s1024"]
    bench = tmp_path / "bench"
    for sub in ("archs", "configs", "workloads"):
        (bench / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "traffic"),
                    bench / "traffic")
    shutil.copy(os.path.join(ROOT, "bench", "archs", "dense.py"),
                bench / "archs" / "copied_block.py")
    model = run.read_json(os.path.join(ROOT, "bench", "configs",
                                       "qwen2-1.5b.json"))
    model.update(name="copied-model", arch="copied_block")
    (bench / "configs" / "copied-model.json").write_text(json.dumps(model))
    shutil.copy(os.path.join(ROOT, "bench", "workloads",
                             "qwen2-1.5b.b4s1024.json"),
                bench / "workloads" / "copied-model.b4s1024.json")
    spec["configs"].append(dict(spec["configs"][0], name="copied-model",
                                file="bench/configs/copied-model.json"))
    spec["workloads"].append(dict(cell, name="copied-model.b4s1024",
                                  config="copied-model"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    tiny = tiny_cell("copied-model.b4s1024", root=str(tmp_path))
    assert tiny["arch"].__file__ == str(bench / "archs" / "copied_block.py")
    result, _ = run.run(tiny, 2**31 + 78, 0.3, require_tpu=False,
                        log=lambda *_: None)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1


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
