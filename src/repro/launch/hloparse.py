"""HLO text parsing + TPU hardware constants (import-side-effect-free).

`launch.dryrun` / `launch.roofline` mutate XLA_FLAGS at import (they must —
the 512-device count locks at first jax init).  Everything other code
needs from them lives here so tests and benchmarks never inherit that
environment mutation into child processes.
"""

from __future__ import annotations

import re

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9_\[\],{}: #*\"]+\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(expr: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(expr):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def normalize_cost_analysis(cost) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (``None`` -> empty)."""
    return dict(cost) if cost is not None else {}


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the per-device
    program (proxy for on-wire traffic per device per step)."""
    out: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group(2).lower()
        out[kind] = out.get(kind, 0) + _shape_bytes(m.group(1))
    return out
