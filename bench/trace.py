"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

The device timeline is the ``XLA Ops`` line of every ``/device:TPU:<n>``
plane; the host's own spans are the ``bench.*`` annotations the harness
writes (``jax.profiler.TraceAnnotation``) on the same clock.  The traced
window is the ``bench.window`` span.  Per device, busy time is the union
of the operations' intervals inside the window; device numbers are
averaged over the devices.  Each stretch of the window in which no
operation ran is split over the innermost ``bench.*`` span the host was
in, which says what the device waited for.
"""

from __future__ import annotations

import re
from collections import defaultdict

WINDOW = "bench.window"
_OP_NAME = re.compile(r"%?([A-Za-z0-9_.\-]+)")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def op_kind(name: str) -> str:
    """An instruction's name without its numeric suffix."""
    return re.sub(r"(\.\d+)+$", "", name)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def summarize(path: str) -> dict | None:
    """Device and host numbers of the trace at ``path``, or ``None``
    where it holds no ``bench.window`` span or no device operation in
    it.  Times are in seconds:

    - ``window_s``: length of the traced window;
    - ``busy_s``: union of operation intervals, mean over devices;
    - ``ops``: ``{instruction name: (seconds, calls)}``, mean over
      devices, each op's whole duration inside the window;
    - ``idle_by_host``: ``{host span: seconds}`` of device idle time,
      mean over devices;
    - ``devices``: how many device timelines were read.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, device_ops = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops.append(
                        [(op_name(ev.name), ev.start_ns,
                          ev.start_ns + ev.duration_ns)
                         for ev in line.events])
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows or not device_ops:
        return None
    lo, hi = windows[0]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    n_dev = len(device_ops)
    busy = 0.0
    ops: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0])
    idle: dict[str, float] = defaultdict(float)
    segments = _host_segments(inner, lo, hi)
    for events in device_ops:
        inside = [(n, s, e) for n, s, e in events if e > lo and s < hi]
        merged = union(_clip(s, e, lo, hi) for _, s, e in inside)
        busy += sum(e - s for s, e in merged)
        for n, s, e in inside:
            ops[n][0] += (e - s) / n_dev
            ops[n][1] += 1 / n_dev
        gaps, t = [], lo
        for s, e in merged:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        _attribute(gaps, segments, idle, n_dev)
    if busy <= 0.0:
        return None
    ns = 1e-9
    return {"window_s": (hi - lo) * ns,
            "busy_s": busy / n_dev * ns,
            "ops": {n: (v[0] * ns, v[1]) for n, v in ops.items()},
            "idle_by_host": {n: v * ns for n, v in idle.items()},
            "devices": n_dev}


def _host_segments(spans, lo, hi) -> list[tuple[float, float, str]]:
    """The window cut at every host span boundary, each piece named
    after the innermost span covering it (``host other`` where none
    does)."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(e - s, n) for n, s, e in spans if s <= mid < e]
        out.append((a, b, min(cover)[1] if cover else "host other"))
    return out


def _attribute(gaps, segments, idle, n_dev) -> None:
    """Add each idle stretch's overlap with each host segment to
    ``idle``; both lists are sorted and disjoint."""
    j = 0
    for g0, g1 in gaps:
        while j < len(segments) and segments[j][1] <= g0:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g1:
            a, b, name = segments[k]
            idle[name] += (min(b, g1) - max(a, g0)) / n_dev
            k += 1


def breakdown(summary: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the ``top`` device operation
    kinds by time, and device idle time by what the host was doing."""
    kinds: dict[str, float] = defaultdict(float)
    for name, (secs, _) in summary["ops"].items():
        kinds[op_kind(name)] += secs
    ranked = sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary["idle_by_host"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ranked],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def kernel_time(summary: dict, prefix: str) -> tuple[float, float]:
    """Seconds and calls of the operations whose name starts with
    ``prefix`` (a Pallas kernel's instruction name)."""
    secs = calls = 0.0
    for name, (s, c) in summary["ops"].items():
        if name.startswith(prefix):
            secs += s
            calls += c
    return secs, calls
