"""Readings behind each cell's limits: sound runs, the control and the
planted faults, at the cell's own size, in one process.

    python3 bench/control.py --workload <cell> --first-seed <n> \\
        [--seeds 12] [--control 3] [--faults 3] [--out <file.jsonl>]

Each reading is one seed's first steps through the benchmark's own
``Trainer`` (``bench/run.py``), compared with the reference as a run
compares them; no window is needed, since the numbers come from the
first steps.  One compiled step serves every reading at one precision.
Readings: ``--seeds`` sound runs; ``--control`` runs of the control, the
program at the next precision below the configuration's
(``jax.default_matmul_precision("high")``, three bfloat16 passes, where
the configuration states "highest"); and ``--faults`` runs of each fault
the cell can have (``bench/faults.py:of``: the general ones and the
architecture's own).  Every reading prints, and is appended to
``--out``, as one JSON line ``{"kind", "seed", "correct", "numbers"}``
with the numbers of ``bench/check.py``.  The benchmark's own runs never
run this; it needs a TPU like them.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import faults  # noqa: E402
from bench import run as bench_run  # noqa: E402

#: the matmul precision one step below the one the configurations state
LOWER = {"highest": "high"}


def readings(cell: dict, plan, out: str | None = None,
             require_tpu: bool = True) -> list[dict]:
    """Run ``plan``, a list of ``(kind, seed)`` with ``kind`` one of
    ``sound``, ``control`` or a fault's name, on one compiled step per
    precision; returns one line per reading."""
    devs = bench_run.devices_for(cell["chips"], require_tpu)
    bench_run.compile_cache()
    quiet = lambda *_: None  # noqa: E731
    trainers, lines = {}, []
    for kind, seed in plan:
        this = cell
        if kind == "control":
            model = dict(cell["model"])
            model["precision"] = dict(model["precision"], matmul=LOWER[
                model["precision"]["matmul"]])
            this = dict(cell, model=model)
        key = this["model"]["precision"]["matmul"]
        if key not in trainers:
            trainers.clear()
            trainers[key] = bench_run.Trainer(this, devs,
                                              bench_run.Spans(), quiet)
        trainer = trainers[key]
        patch = faults.of(cell["arch"]).get(kind, contextlib.nullcontext)
        with patch():
            prog = trainer.start(seed, quiet)
        trainer.release()
        ok, nums = bench_run.compare(this, seed, prog, quiet)
        line = {"kind": kind, "seed": seed, "correct": ok,
                "numbers": {n: v for n, (v, _) in nums.items()},
                "at": {n: at for n, (_, at) in nums.items()}}
        print(json.dumps(line), flush=True)
        if out:
            with open(out, "a") as f:
                f.write(json.dumps(line) + "\n")
        lines.append(line)
    return lines


def plan_of(cell: dict, first_seed: int, seeds: int, control: int,
            fault_runs: int) -> list[tuple[str, int]]:
    """``(kind, seed)`` of each reading, seeds counting up from
    ``first_seed``: the sound runs, each fault of the cell but the
    unchanged state (it reads 1 by the measure of ``grad``, with no
    run), then the control."""
    counts = [("sound", seeds)] + \
        [(name, fault_runs) for name in faults.of(cell["arch"])
         if name != "unchanged_state"] + [("control", control)]
    plan, seed = [], first_seed
    for kind, count in counts:
        for _ in range(count):
            plan.append((kind, seed))
            seed += 1
    return plan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    readings(cell, plan_of(cell, args.first_seed, args.seeds,
                           args.control, args.faults), args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
