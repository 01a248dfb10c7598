"""The harness end to end at CPU size: its refusal of a CPU platform,
a sound run's result line, and ``correct`` coming out false under each
fault planted beneath the timed path."""

import dataclasses
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


#: appended to the copied dense module: layer 0's residual after
#: attention stands in for a routing choice (the reference follows
#: nothing, and its gap is the largest relative difference from its own
#: value), and a fault of SwiGLU's own
OWN_PARTS = '''

def choices(m):
    return ["l0/x_attn"]


_dense_loss = loss


def loss(params, ids, labels, m, choices=None):
    value = _dense_loss(params, ids, labels, m)
    if choices is None:
        return value
    x = params["embed"][ids]
    p = {n.split("/", 1)[1]: w for n, w in params.items()
         if n.startswith("l0/")}
    own = x + attention(rms_norm(x, p["attn_norm"], m["rms_norm_eps"]), p, m)
    gap = jnp.max(jnp.abs(choices["l0/x_attn"] - own)) / jnp.max(
        jnp.abs(own))
    return value, gap


def gate_up_swapped():
    """Layer 0's gate and up projections are swapped as they load."""
    from repro.api.session import Session

    from bench import faults

    def make(orig):
        def load(self, values):
            values = dict(values)
            values["l0/w_up"], values["l0/w_gate"] = \\
                values["l0/w_gate"], values["l0/w_up"]
            return orig(self, values)
        return load

    return faults._patched(Session, "load", make)


FAULTS = {"gate_up_swapped": gate_up_swapped}
'''

#: the copied module's limit on ``choice``: its sound tiny run reads
#: 7.2e-8 (float32 rounding), a change of a part in a thousand 1e-3
COPIED_CHOICE = 1e-5


def altered_choice():
    """The choices the step fetches are changed where they are made,
    by a part in a thousand, and nothing else is."""
    from repro.api.session import Session

    def make(orig):
        def train_step(self, feeds=None, **kw):
            out = orig(self, feeds, **kw)
            out.outputs = {n: dataclasses.replace(t, parts={
                d: p * 1.001 for d, p in t.parts.items()})
                for n, t in out.outputs.items()}
            return out
        return train_step

    return faults._patched(Session, "train_step", make)


def test_a_new_architecture_needs_no_edit(tmp_path):
    """A new architecture is a module, a configuration naming it and a
    cell: a root holding a renamed copy of the dense module, given
    discrete choices and a fault of its own, and only new files beside
    the benchmark's own runs a tiny cell correct, with ``choice`` among
    its numbers; its own fault, and a change to the fetched choices,
    each read not correct, the second through ``choice`` alone."""
    spec = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    (cell,) = [w for w in spec["workloads"]
               if w["name"] == "qwen2-1.5b.b4s1024"]
    bench = tmp_path / "bench"
    for sub in ("archs", "configs", "workloads"):
        (bench / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "traffic"),
                    bench / "traffic")
    with open(os.path.join(ROOT, "bench", "archs", "dense.py")) as f:
        (bench / "archs" / "copied_block.py").write_text(
            f.read() + OWN_PARTS)
    model = run.read_json(os.path.join(ROOT, "bench", "configs",
                                       "qwen2-1.5b.json"))
    model.update(name="copied-model", arch="copied_block")
    (bench / "configs" / "copied-model.json").write_text(json.dumps(model))
    work = run.read_json(os.path.join(ROOT, "bench", "workloads",
                                      "qwen2-1.5b.b4s1024.json"))
    work["limits"]["choice"] = COPIED_CHOICE
    (bench / "workloads" / "copied-model.b4s1024.json").write_text(
        json.dumps(work))
    spec["configs"].append(dict(spec["configs"][0], name="copied-model",
                                file="bench/configs/copied-model.json"))
    spec["workloads"].append(dict(cell, name="copied-model.b4s1024",
                                  config="copied-model"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    tiny = tiny_cell("copied-model.b4s1024", root=str(tmp_path))
    assert tiny["arch"].__file__ == str(bench / "archs" / "copied_block.py")
    result, nums = run.run(tiny, 2**31 + 78, 0.3, require_tpu=False,
                           log=lambda *_: None)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(nums) == {"loss", "grad", "change", "choice"}
    assert result["checks"]["choice"]["limit"] == COPIED_CHOICE
    # the checked steps and the window run one step program, which
    # fetches the choices; the record keeps the checked steps' only
    prog, fetched, _ = _checked_and_window_steps(tiny, 2**31 + 78)
    assert fetched == [("l0/x_attn",)] * (run.FIRST_STEPS + 1)
    assert len(prog["choices"]) == run.FIRST_STEPS

    tiny["arch"].FAULTS["altered_choice"] = altered_choice
    plan = control.plan_of(tiny, 2**31 + 79, 1, 0, 1)
    assert [k for k, _ in plan] == ["sound", "half_batch", "altered_token",
                                    "gate_up_swapped", "altered_choice"]
    lines = {line["kind"]: line for line in control.readings(
        tiny, [k for k in plan if k[0] in ("gate_up_swapped",
                                           "altered_choice")],
        require_tpu=False)}
    assert lines["gate_up_swapped"]["correct"] is False
    assert lines["gate_up_swapped"]["numbers"]["loss"] > 6e-6
    altered = lines["altered_choice"]
    assert altered["correct"] is False
    assert altered["numbers"]["choice"] > COPIED_CHOICE
    assert all(altered["numbers"][n] <= lim
               for n, lim in tiny["limits"].items() if n != "choice")


FAULTS = ["unchanged_state", "half_batch", "altered_token",
          "bfloat16_weights"]


@pytest.fixture(scope="module",
                params=["qwen2-1.5b.b4s1024", "qwen2-1.5b.b1s4096"])
def readings(request):
    """A sound seed, then each fault, on one compiled step of each cell
    (``qwen2-1.5b.b1s4096``'s one row: ``half_batch`` leaves out half
    its positions).  ``bfloat16_weights`` stands in for the control
    here: XLA:CPU ignores the matmul precision that the control lowers
    on the chip."""
    cell = tiny_cell(request.param)
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


def _checked_and_window_steps(cell: dict, seed: int):
    """Set-up's checked steps and one window step of ``cell``: the
    program's record, the ``fetches`` each step passed to
    ``Session.train_step``, and the keys of the step programs the
    executor lowered."""
    from repro.api.session import Session

    fetched = []

    def make(orig):
        def train_step(self, feeds=None, **kw):
            fetched.append(tuple(kw["fetches"]))
            return orig(self, feeds, **kw)
        return train_step

    trainer = run.Trainer(cell, run.devices_for(1, False), run.Spans(),
                          lambda *_: None)
    with faults._patched(Session, "train_step", make):
        prog = trainer.start(seed, lambda *_: None)
        trainer.step(seed, run.FIRST_STEPS)
    trainer.release()
    keys = set(trainer.sess.executor._cache[
        trainer.sess.program.compile_train(0)])
    assert keys == {(tuple(trainer.lw.fetches), 1)}
    return prog, fetched, keys


def test_a_dense_record_has_no_choices():
    """Without ``choices`` in its module a cell's first steps run the
    step the window runs, with no extra fetch, and its record and
    numbers are the three they always were."""
    cell = tiny_cell()
    assert run.choice_names(cell["arch"], cell["model"]) == []
    prog, fetched, _ = _checked_and_window_steps(cell, 2**31 + 80)
    assert fetched == [()] * (run.FIRST_STEPS + 1)
    assert set(prog) == {"loss", "grad", "change"}
    _, nums = run.compare(cell, 2**31 + 80, prog, lambda *_: None)
    assert set(nums) == {"loss", "grad", "change"}

