"""The numbers that decide ``correct``, taken alike from the program's
first steps and from the reference's.

A record of three training steps holds each step's loss and, per leaf,
the norm of the first gradient as the optimizer got it and the norm of
the parameters' change after the three steps.  Norms are summed in
float64.

The numbers compared:

- ``loss``: the largest relative gap of a step's loss;
- ``grad``: over the leaves, the largest gap between the program's and
  the reference's norm of the first gradient, over the larger of the
  reference's norm of that leaf and of the median leaf;
- ``change``: the same for the norm of the change after three steps,
  over the leaves whose reference gradient is at least a thousandth of
  the median leaf's (a key's bias gets no gradient under softmax, so
  AdamW moves it by round-off alone);
- ``choice``, only where the architecture makes discrete choices (its
  module's ``choices``, see ``bench/run.py``): the largest ``gap`` over
  the steps between the program's choices, which the reference follows,
  and the ones the reference's own arithmetic makes.
"""

from __future__ import annotations

import numpy as np

#: a leaf whose reference gradient norm is under this share of the
#: median leaf's moves by round-off alone and is not held to ``change``
MOVING = 1e-3


#: entries summed at a time, so that no leaf is copied whole
CHUNK = 1 << 22


def norm(a, b=None) -> float:
    """The norm of ``a`` (of ``a - b`` where ``b`` is given), summed in
    float64 a chunk at a time."""
    a = np.asarray(a).reshape(-1)
    b = None if b is None else np.asarray(b).reshape(-1)
    total = 0.0
    for i in range(0, a.size, CHUNK):
        c = a[i:i + CHUNK].astype(np.float64)
        if b is not None:
            c -= b[i:i + CHUNK]
        total += float(np.dot(c, c))
    return float(np.sqrt(total))


def _worst(prog: dict, ref: dict, leaves) -> tuple[float, str]:
    floor = float(np.median(list(ref.values())))
    return max((abs(prog[n] - ref[n]) / max(ref[n], floor), n)
               for n in leaves)


def numbers(prog: dict, ref: dict) -> dict[str, tuple[float, str]]:
    """``{number: (value, where)}`` comparing two records."""
    losses = [(abs(p - r) / abs(r), f"step {i}") for i, (p, r) in
              enumerate(zip(prog["loss"], ref["loss"]))]
    g_floor = float(np.median(list(ref["grad"].values())))
    moving = [n for n, g in ref["grad"].items() if g >= MOVING * g_floor]
    nums = {
        "loss": max(losses),
        "grad": _worst(prog["grad"], ref["grad"], ref["grad"]),
        "change": _worst(prog["change"], ref["change"], moving),
    }
    if "gap" in ref:
        nums["choice"] = max((g, f"step {i}")
                             for i, g in enumerate(ref["gap"]))
    return nums


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """Whether every number with a limit is within it, and
    ``{name: {"value", "limit", "at"}}`` of the numbers compared.  A
    cell may leave out a number that has no upper reading (PERF.md
    names it with its readings); a limit without its number, or a
    ``choice`` without a limit, is an error: a cell whose architecture
    makes choices holds them to a limit."""
    missing = sorted(set(limits) - set(nums)) + \
        (["choice"] if "choice" in nums and "choice" not in limits else [])
    if missing:
        raise ValueError(f"numbers {sorted(nums)} against limits "
                         f"{sorted(limits)}: {missing} unmatched")
    checks = {n: {"value": nums[n][0], "limit": lim, "at": nums[n][1]}
              for n, lim in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
