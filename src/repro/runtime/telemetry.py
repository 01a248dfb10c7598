"""What a training step records about itself: host spans on the
profiler's clock, counters and gauges, kept in memory.

:func:`step` wraps one train step in a ``StepTraceAnnotation``
(``hspmd.train_step``) and opens a :class:`StepRecord`.  Inside it,
:func:`span` is a ``TraceAnnotation`` (``hspmd.<name>``) whose host
seconds also add to the record; :func:`count` adds to a number in it
and :func:`gauge` sets one; :func:`hold` names host buffers the program
keeps from step to step, and the record's ``host_state_bytes`` gauge
sums them, each underlying buffer once however many views share it.
On exit the record joins a bounded deque, :func:`recent_steps`.

The spans always run and cost microseconds; nothing is written to disk.
Whether they land in a trace is the profiler's decision
(``jax.profiler.trace``), where they share a clock with the device's
planes.  Outside a step, spans still annotate a trace, and counters,
gauges and holds are dropped.  Steps do not nest: one record is open at
a time.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from repro.core.simulator import ShardedTensor

PREFIX = "hspmd."
#: step records kept in memory, newest last
KEEP = 1024


@dataclass
class StepRecord:
    """One train step.  ``step`` numbers the process's steps from 0;
    ``updates`` is the optimizer update the step makes (1 for the first
    on fresh optimizer state); ``seconds`` is its host wall time;
    ``spans`` holds host seconds by span name, ``counts`` and
    ``gauges`` numbers by name."""

    step: int
    updates: int
    seconds: float = 0.0
    spans: dict[str, float] = field(default_factory=dict)
    counts: dict[str, int] = field(default_factory=dict)
    gauges: dict[str, float] = field(default_factory=dict)


_records: deque[StepRecord] = deque(maxlen=KEEP)
_numbers = itertools.count()
_open: StepRecord | None = None
_held: set[int] = set()         # ids of the open step's held buffers


def recent_steps() -> deque[StepRecord]:
    """The last :data:`KEEP` finished step records, oldest first."""
    return _records


@contextmanager
def step(updates: int):
    """Record one train step (see :class:`StepRecord`); the record is
    kept only when the step finishes without raising."""
    global _open
    from jax.profiler import StepTraceAnnotation

    rec = StepRecord(next(_numbers), updates)
    _open = rec
    t0 = time.perf_counter()
    try:
        with StepTraceAnnotation(PREFIX + "train_step", step_num=rec.step):
            yield rec
    finally:
        _open = None
        _held.clear()
        rec.seconds = time.perf_counter() - t0
    _records.append(rec)


@contextmanager
def span(name: str):
    """A ``hspmd.<name>`` profiler annotation whose host seconds add to
    the open step's ``spans[name]``."""
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    try:
        with TraceAnnotation(PREFIX + name):
            yield
    finally:
        rec = _open
        if rec is not None:
            rec.spans[name] = rec.spans.get(name, 0.0) \
                + time.perf_counter() - t0


def count(name: str, n: int) -> None:
    """Add ``n`` to the open step's ``counts[name]``."""
    if _open is not None:
        _open.counts[name] = _open.counts.get(name, 0) + int(n)


def gauge(name: str, value: float) -> None:
    """Set the open step's ``gauges[name]``."""
    if _open is not None:
        _open.gauges[name] = value


def hold(*objs) -> None:
    """Add the host buffers under ``objs`` (arrays, ShardedTensors and
    dicts, lists or tuples of them) to the open step's
    ``host_state_bytes``; a buffer already counted in this step, also
    through another view of it, is not counted again."""
    rec = _open
    if rec is None:
        return
    added = 0
    todo = list(objs)
    while todo:
        obj = todo.pop()
        if isinstance(obj, np.ndarray):
            while isinstance(obj.base, np.ndarray):
                obj = obj.base
            if id(obj) not in _held:
                _held.add(id(obj))
                added += obj.nbytes
        elif isinstance(obj, ShardedTensor):
            todo.extend(obj.parts.values())
        elif isinstance(obj, dict):
            todo.extend(obj.values())
        elif isinstance(obj, (list, tuple)):
            todo.extend(obj)
    gauge("host_state_bytes",
          rec.gauges.get("host_state_bytes", 0) + added)
