"""Async MPMD execution: one XLA program per (virtual) pipeline stage.

``runtime.program.LoweredGraph`` lowers the whole graph — every stage,
every microbatch — into ONE scanned ``shard_map`` program; XLA's
dependence order realizes the pipeline, but every P2P send and every
grad all-reduce serializes inside that single dispatch.  This module is
the MPMD alternative (JaxPP direction): the graph's ops are bucketed by
``(virtual stage, phase)`` (``core.schedule.assign_stages`` — exactly
the buckets the SimulatorExecutor's timetable ticks execute), each
bucket compiles to its OWN ``shard_map`` program over the same 1-D
mesh, and the dispatch loop walks the explicit 1F1B / GPipe /
interleaved timetable issuing programs as their inputs become ready:

* **per-stage programs** — a bucket's compute ops lower through the
  SAME specialization-class emission as the scanned program
  (``runtime.program.emit_segment`` over a ``partition_graph`` of the
  bucket's ops), so per-class branches, dtype chains and pad/unpad
  slicing are bitwise identical to the single-program path,
* **double-buffered P2P** — stage-boundary comm ops (activation sends,
  cotangent sends, interleaved wrap-arounds) are split OUT of the
  receiving stage's program into :class:`CommChannel`\\ s issued eagerly
  the moment the producing tick's program is dispatched; jax's async
  dispatch then moves microbatch ``j+1``'s activations while microbatch
  ``j``'s tick computes, through a bounded 2-slot in-flight window
  (issuing a third send blocks on the oldest — real back-pressure),
* **grad-reduce overlapped into backward** — a backward tick's trailing
  grad-reduce comm (output unconsumed inside the bucket) is hoisted out
  of the stage program and issued immediately after the tick, so the
  reduce rendezvous overlaps the NEXT tick's compute instead of
  serializing the epilogue.

One platform constraint shapes the dispatch loop: XLA's host-CPU
collectives rendezvous through a shared thread pool, and two
concurrently executing collective-bearing computations can park their
threads at different rendezvous until neither can proceed.  The loop
therefore keeps at most ONE collective-bearing computation in flight
(``AsyncLoweredGraph._coll_window``) — compute-only stage programs and
host-side dispatch still overlap it, and since the window only ever
adds blocking, numerics are unchanged.

Splitting a comm op out of its stage program never changes numerics:
the channel program traces the identical ``PlanLowering.apply`` on the
identical stacked buffers, and the scanned program's batched uniform-
reduce flush is documented bit-identical to one-at-a-time emission —
which is why ``AsyncExecutor`` is differentially bit-exact against BOTH
existing executors (``async:*`` selftest cases).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

from repro.core.graph import Graph
from repro.core.lowered_ir import CommSlot, partition_graph
from repro.core.schedule import (SCHEDULES, PipelineSchedule, ScheduleError,
                                 assign_stages, infer_virtual_stages)
from repro.core.simulator import ShardedTensor
from repro.core.specialize import construct_pipelines, resolve_comm_ops
from repro.core.symbolic import bind_shape
from repro.core.topology import Topology
from repro.kernels.policy import select_attention_impl_per_class

from .lowering import (DeviceOrder, LoweringStats, PlanLowering, maybe_x64,
                       pack_shards, pad_shape)
from .program import emit_segment, fetch_rows, segment_liveness, unpack_rows


def _phase_of(op) -> str:
    return "bwd" if op.attrs.get("phase") == "bwd" else "fwd"


@dataclass
class StageProgram:
    """One (virtual stage, phase) bucket compiled to its own jitted
    ``shard_map`` program: ``fn(*in_buffers) -> out_buffers``, all
    stacked ``(mesh, *pad)`` arrays."""

    stage: int
    phase: str
    ops: list
    in_names: list[str]
    out_names: list[str]
    fn: object
    # True when the bucket's partitioned IR kept inline comm ops (e.g.
    # a tp all-reduce inside the stage): such programs enter the global
    # one-in-flight collective window in ``_execute``
    has_collectives: bool = True


@dataclass
class CommChannel:
    """A comm op split out of its stage program and issued eagerly at
    the tick that produces its input.

    ``kind`` is ``"p2p"`` (activation / cotangent / wrap-around send)
    or ``"reduce"`` (grad-reduce and other reducing plans).  ``slots``
    bounds the in-flight window: issuing past it blocks on the oldest
    outstanding transfer first (the double-buffer discipline)."""

    op: object
    kind: str
    trigger: tuple[int, str]
    in_name: str
    out_name: str
    fn: object
    slots: int = 2
    inflight: deque = field(default_factory=deque)


class AsyncLoweredGraph:
    """A deduced graph + strategy compiled to one program per (virtual
    stage, phase) bucket plus split-out comm channels, dispatched
    asynchronously over an explicit timetable.

    The same graph/strategy/shape machinery as
    :class:`~repro.runtime.program.LoweredGraph`, but instead of one
    scanned whole-mesh program the lowering re-partitions each bucket's
    ops separately (``partition_graph(..., ops=bucket)`` — a whole-graph
    segment may span a stage/phase boundary with no comm op on it, e.g.
    the last stage's loss where fwd flows straight into bwd) and the
    explicit timetable that is only advisory for the scanned program
    becomes the actual dispatch order here."""

    def __init__(self, graph: Graph, strategy: int = 0, *,
                 shape_env: dict[str, int] | None = None, mesh=None,
                 topology: Topology | None = None,
                 reduction: str = "exact", fetches=None,
                 virtual_stages_per_device: int | None = None):
        self.graph = graph
        self.k = strategy
        self.reduction = reduction
        self.serialize = False      # block after every issue (bench knob)
        env = shape_env or {}
        self.shapes = {name: bind_shape(t.shape, env)
                       for name, t in graph.tensors.items()}
        resolved = resolve_comm_ops(graph, strategy, topology, shape_env)
        self._plans = {id(rc.op): rc.plan for rc in resolved}
        self.pipelines = construct_pipelines(graph, strategy,
                                             resolved_comms=resolved)
        self.n_stages = max((p.n_stages for p in self.pipelines),
                            default=1)
        inferred = infer_virtual_stages(graph, strategy, self.pipelines)
        self.v = inferred if virtual_stages_per_device is None \
            else virtual_stages_per_device
        self.n_virtual = self.n_stages * self.v
        # raises ScheduleError when the graph wraps more than v allows
        stage_of = assign_stages(graph, strategy, self.pipelines,
                                 virtual_stages_per_device=self.v)
        self._pack_bufs: dict[str, np.ndarray] = {}
        # the global collective window (see _execute): outputs of the
        # most recently issued collective-bearing computation
        self._inflight_coll: deque = deque()

        devs: set[int] = set()
        for t in graph.tensors.values():
            if t.annots:
                devs |= set(t.annots[strategy].devices)
        for plan in self._plans.values():
            for annot in plan.annots:
                devs |= set(annot.devices)
        self.order = DeviceOrder(tuple(sorted(devs)))

        if mesh is None:
            from repro.launch.mesh import make_runtime_mesh
            mesh = make_runtime_mesh(len(self.order))
        self.mesh = mesh
        self.n_mesh = int(mesh.devices.size)
        self.platform = mesh.devices.flat[0].platform
        if self.n_mesh < len(self.order):
            from repro.launch.mesh import device_shortfall
            raise ValueError(device_shortfall(
                "graph", len(self.order), self.n_mesh, self.platform))
        self.axis = mesh.axis_names[0]

        self.leaves = [o.outputs[0] for o in graph.ops
                       if o.kind in ("placeholder", "parameter")]
        self._per_mb = {t.name for t in self.leaves
                        if t.producer is not None
                        and t.producer.kind == "placeholder"}
        self.fetches = list(fetches or [t.name for t in graph.sinks()])
        for f in self.fetches:
            if f not in graph.tensors:
                raise ValueError(f"unknown fetch tensor {f!r}")

        self._consumers: dict[str, set[int]] = {}
        for op in graph.ops:
            for t in op.inputs:
                self._consumers.setdefault(t.name, set()).add(id(op))

        k, shapes = strategy, self.shapes

        def impl_of(op, dev):
            if op.kind != "attention":
                return ""
            qs = shapes[op.inputs[0].name]
            ks = shapes[op.inputs[1].name]
            return select_attention_impl_per_class(
                tuple(op.inputs[0].annots[k].device_shape(dev, qs)),
                tuple(op.inputs[1].annots[k].device_shape(dev, ks)),
                self.platform)

        # bucket the schedulable ops exactly like the simulator's ticks
        buckets: dict[tuple[int, str], list] = {}
        for op in graph.ops:
            if op.kind in ("placeholder", "parameter"):
                continue
            buckets.setdefault(
                (stage_of[id(op)], _phase_of(op)), []).append(op)

        self.stats = LoweringStats()
        self.programs: dict[tuple[int, str], StageProgram] = {}
        self.channels: list[CommChannel] = []
        # (stage, phase) -> channels issued right after that tick
        self.triggers: dict[tuple[int, str], list[CommChannel]] = {}

        for key in sorted(buckets):
            ops = buckets[key]
            # classify each comm op: split OUT of the stage program when
            # its input crosses a bucket boundary (boundary P2P) or its
            # output escapes the bucket untouched (trailing grad-reduce
            # / wrap-around send); walk in reverse so a comm op's
            # in-bucket consumers are already classified
            status: dict[int, str] = {}
            for op in reversed(ops):
                if op.kind != "comm":
                    status[id(op)] = "inline"
                    continue
                producer = graph.tensors[op.inputs[0].name].producer
                leaf = producer is None or \
                    producer.kind in ("placeholder", "parameter")
                pb = key if leaf else \
                    (stage_of[id(producer)], _phase_of(producer))
                if pb != key:
                    status[id(op)] = "split"
                    continue
                out = op.outputs[0].name
                consumed_inline = any(
                    status.get(cid) == "inline"
                    for cid in self._consumers.get(out, ()))
                status[id(op)] = "inline" if consumed_inline else "split"
            inline_ops = [op for op in ops if status[id(op)] == "inline"]
            for op in ops:
                if status[id(op)] != "split":
                    continue
                producer = graph.tensors[op.inputs[0].name].producer
                leaf = producer is None or \
                    producer.kind in ("placeholder", "parameter")
                trigger = key if leaf else \
                    (stage_of[id(producer)], _phase_of(producer))
                ch = self._compile_channel(op, trigger)
                self.channels.append(ch)
                self.triggers.setdefault(trigger, []).append(ch)
            prog = self._compile_bucket(key, inline_ops, impl_of)
            if prog is not None:
                self.programs[key] = prog
        self._counted_ops = sum(len(p.ops)
                                for p in self.programs.values()) \
            + len(self.channels)

    # -- compilation -------------------------------------------------------

    def _plan_lowering(self, op) -> PlanLowering:
        pl = PlanLowering(self._plans[id(op)],
                          self.shapes[op.inputs[0].name], self.order,
                          self.axis, self.n_mesh,
                          reduction=self.reduction)
        self.stats.merge(pl.stats)
        return pl

    def _compile_channel(self, op, trigger) -> CommChannel:
        import jax
        from jax.sharding import PartitionSpec as P

        pl = self._plan_lowering(op)
        axis = self.axis

        def body(block):
            x = block[0]
            i = jax.lax.axis_index(axis)
            return pl.apply(x, i, x.dtype)[None]

        spec = P(axis, *([None] * len(self.shapes[op.inputs[0].name])))
        jitted = jax.jit(jax.shard_map(body, mesh=self.mesh, in_specs=spec,
                                       out_specs=spec, check_vma=False))
        fn = maybe_x64(jitted,
                       pl.needs_x64 and self.reduction == "exact")
        return CommChannel(
            op, "reduce" if pl.has_reduce else "p2p", trigger,
            op.inputs[0].name, op.outputs[0].name, fn)

    def _compile_bucket(self, key, inline_ops, impl_of
                        ) -> StageProgram | None:
        import jax
        from jax.sharding import PartitionSpec as P

        if not inline_ops:
            return None
        graph, k, shapes = self.graph, self.k, self.shapes
        order, n_mesh, axis = self.order, self.n_mesh, self.axis
        inline_ids = {id(op) for op in inline_ops}
        produced = {op.outputs[0].name for op in inline_ops}
        in_names: list[str] = []
        for op in inline_ops:
            for t in op.inputs:
                if t.name not in produced and t.name not in in_names:
                    in_names.append(t.name)
        fetch_set = set(self.fetches)
        out_names = [
            op.outputs[0].name for op in inline_ops
            if op.outputs[0].name in fetch_set
            or (self._consumers.get(op.outputs[0].name, set())
                - inline_ids)]
        if not out_names:
            return None             # dead bucket: nothing escapes

        ir = partition_graph(graph, k, shapes=shapes, impl_of=impl_of,
                             devices=order.devices, ops=inline_ops)
        seg_live = segment_liveness(graph, ir.segments, out_names)
        extra_idle = n_mesh > len(order)
        for seg in ir.segments:
            if not seg_live[id(seg)][1]:
                continue
            self.stats.compute_segments += 1
            if seg.is_homogeneous() and not extra_idle:
                self.stats.straightline_segments += 1
            else:
                idle = 1 if (seg.idle_devices or extra_idle) else 0
                self.stats.switch_branches_emitted += \
                    seg.n_classes + idle
            for cls in seg.classes:
                for op, spec in zip(seg.ops, cls.specs):
                    if op.kind == "attention" and spec is not None:
                        if spec.impl == "pallas":
                            self.stats.pallas_dispatches += 1
                        else:
                            self.stats.ref_dispatches += 1
        lowerings: dict[int, PlanLowering] = {}
        needs_x64 = False
        for entry in ir.entries:
            if isinstance(entry, CommSlot):
                pl = self._plan_lowering(entry.op)
                lowerings[id(entry.op)] = pl
                needs_x64 |= pl.needs_x64

        def body(*blocks):
            i = jax.lax.axis_index(axis)
            tenv = {n: b[0] for n, b in zip(in_names, blocks)}
            for entry in ir.entries:
                if isinstance(entry, CommSlot):
                    op = entry.op
                    x = tenv[op.inputs[0].name]
                    tenv[op.outputs[0].name] = \
                        lowerings[id(op)].apply(x, i, x.dtype)
                else:
                    emit_segment(entry, tenv, i, seg_live=seg_live,
                                 graph=graph, k=k, shapes=shapes,
                                 order=order, n_mesh=n_mesh,
                                 platform=self.platform)
            return tuple(tenv[n][None] for n in out_names)

        in_specs = tuple(P(axis, *([None] * len(shapes[n])))
                         for n in in_names)
        out_specs = tuple(P(axis, *([None] * len(shapes[n])))
                          for n in out_names)
        jitted = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                       in_specs=in_specs,
                                       out_specs=out_specs,
                                       check_vma=False))
        fn = maybe_x64(jitted, needs_x64 and self.reduction == "exact")
        return StageProgram(key[0], key[1], list(inline_ops), in_names,
                            out_names, fn,
                            has_collectives=bool(lowerings))

    # -- introspection -----------------------------------------------------

    def describe(self) -> str:
        lines = [f"{len(self.programs)} stage program(s), "
                 f"{len(self.channels)} comm channel(s) over "
                 f"{self.n_virtual} virtual stage(s) "
                 f"(S={self.n_stages}, v={self.v})"]
        for key in sorted(self.programs):
            p = self.programs[key]
            lines.append(
                f"  [{p.phase} vstage {p.stage}] {len(p.ops)} op(s): "
                f"{len(p.in_names)} in -> {len(p.out_names)} out")
        for ch in self.channels:
            lines.append(
                f"  channel {ch.kind} {ch.in_name} -> {ch.out_name} "
                f"(after {ch.trigger[1]} vstage {ch.trigger[0]})")
        return "\n".join(lines)

    # -- pack / execute / fetch --------------------------------------------

    def _pack(self, st: ShardedTensor, annot, shape,
              buf_key: str | None = None) -> np.ndarray:
        out = self._pack_bufs.get(buf_key) if buf_key else None
        stacked = pack_shards(st.parts, annot, shape, self.n_mesh,
                              self.order, out=out)
        if buf_key:
            self._pack_bufs[buf_key] = stacked
        return stacked

    def _put_all(self, blocks: list[np.ndarray]):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = self.axis
        shardings = [
            NamedSharding(self.mesh, P(axis, *([None] * (b.ndim - 1))))
            for b in blocks]
        return jax.device_put(blocks, shardings)

    def _make_envs(self, states) -> list[dict]:
        m = len(states)
        blocks: list[np.ndarray] = []
        slots: list[tuple[int | None, str]] = []
        for t in self.leaves:
            annot = t.annots[self.k]
            shape = self.shapes[t.name]
            if t.name in self._per_mb and m > 1:
                for j, st in enumerate(states):
                    if t.name not in st:
                        raise ValueError(
                            f"missing leaf tensor {t.name!r}")
                    blocks.append(self._pack(st[t.name], annot, shape,
                                             buf_key=f"{t.name}#{j}"))
                    slots.append((j, t.name))
            else:
                if t.name not in states[0]:
                    raise ValueError(f"missing leaf tensor {t.name!r}")
                blocks.append(self._pack(states[0][t.name], annot,
                                         shape, buf_key=t.name))
                slots.append((None, t.name))
        puts = self._put_all(blocks)
        envs: list[dict] = [{} for _ in range(m)]
        for (j, name), arr in zip(slots, puts):
            if j is None:
                for env in envs:
                    env[name] = arr
            else:
                envs[j][name] = arr
        return envs

    def _coll_window(self) -> None:
        """Admit one more collective-bearing computation.

        XLA's host-CPU collectives rendezvous through a shared thread
        pool: two computations whose collectives span overlapping
        device sets can execute concurrently, each parking threads at
        its own rendezvous until neither can finish (observed as a
        live process stuck at ``AllReduce``/``AllGather`` rendezvous
        forever).  The cure that preserves MPMD overlap: keep at most
        ONE collective-bearing computation in flight — block on the
        previous one's outputs before issuing the next.  Compute-only
        stage programs and host-side dispatch still overlap freely,
        and numerics are untouched (this only ever adds blocking)."""
        while self._inflight_coll:
            self._inflight_coll.popleft().block_until_ready()

    def _execute(self, ticks, envs) -> None:
        """Walk ``(stage, microbatch, phase)`` ticks in order: issue the
        tick's stage program, then eagerly issue every channel whose
        input that tick produced.  Nothing blocks except the channel
        back-pressure window, the one-in-flight collective window
        (``_coll_window``) and the final fetch — jax's async dispatch
        is what overlaps a channel's collective with the next tick's
        compute."""
        for ch in self.channels:
            ch.inflight.clear()
        self._inflight_coll.clear()
        ran = [0] * len(envs)
        for stage, mb, phase in ticks:
            env = envs[mb]
            key = (stage, phase)
            prog = self.programs.get(key)
            if prog is not None:
                try:
                    ins = [env[n] for n in prog.in_names]
                except KeyError as e:
                    raise ScheduleError(
                        f"stage {stage} ({phase}) ran before its input "
                        f"{e} was produced (invalid schedule)") from None
                if prog.has_collectives:
                    self._coll_window()
                outs = prog.fn(*ins)
                if self.serialize:
                    for y in outs:
                        y.block_until_ready()
                elif prog.has_collectives:
                    self._inflight_coll.extend(outs)
                env.update(zip(prog.out_names, outs))
                ran[mb] += len(prog.ops)
            for ch in self.triggers.get(key, ()):
                x = env.get(ch.in_name)
                if x is None:
                    raise ScheduleError(
                        f"stage {stage} ({phase}) ran before its input "
                        f"'{ch.in_name}' was produced (invalid "
                        f"schedule)")
                if len(ch.inflight) >= ch.slots:
                    ch.inflight.popleft().block_until_ready()
                self._coll_window()
                y = ch.fn(x)
                if self.serialize:
                    y.block_until_ready()
                else:
                    ch.inflight.append(y)
                    self._inflight_coll.append(y)
                env[ch.out_name] = y
                ran[mb] += 1
        if any(r != self._counted_ops for r in ran):
            raise ScheduleError(
                f"schedule executed {ran} of {self._counted_ops} ops "
                f"per microbatch")

    def _fetch(self, envs) -> list[dict[str, ShardedTensor]]:
        results = []
        for env in envs:
            outs = []
            for f in self.fetches:
                if f not in env:
                    raise ScheduleError(
                        f"fetch {f!r} was never produced (invalid "
                        f"schedule)")
                outs.append(env[f])
            rows = fetch_rows(outs, self.n_mesh)
            results.append({
                f: unpack_rows(self.graph, self.k, self.shapes,
                               self.order, f, r)
                for f, r in zip(self.fetches, rows)})
        return results

    def run(self, state: dict[str, ShardedTensor]
            ) -> dict[str, ShardedTensor]:
        """Unpipelined execution (one microbatch): dispatch the buckets
        in the canonical fwd 0..nv-1 then bwd nv-1..0 order."""
        envs = self._make_envs([state])
        nv = self.n_virtual
        order = [(s, 0, "fwd") for s in range(nv)] \
            + [(s, 0, "bwd") for s in reversed(range(nv))]
        self._execute(order, envs)
        return self._fetch(envs)[0]

    def run_schedule(self, schedule: PipelineSchedule, states
                     ) -> list[dict[str, ShardedTensor]]:
        """Dispatch an explicit timetable over per-microbatch states."""
        if len(states) != schedule.num_microbatches:
            raise ScheduleError(
                f"{len(states)} microbatch states for a "
                f"{schedule.num_microbatches}-microbatch schedule")
        envs = self._make_envs(list(states))
        self._execute([(t.stage, t.microbatch, t.phase)
                       for t in schedule.ticks], envs)
        return self._fetch(envs)


class AsyncExecutor:
    """MPMD per-stage dispatch on real devices (the third executor).

    Same contract as ``SimulatorExecutor`` / ``JaxExecutor`` —
    ``{name: ShardedTensor}`` in, per-microbatch fetches out, bit-exact
    against both — but the explicit timetable is the actual dispatch
    order: per-stage programs launch as their inputs arrive, boundary
    P2P moves through double-buffered channels, and grad-reduces issue
    eagerly inside the backward wave.  ``serialize=True`` blocks after
    every issue (the sync baseline the overlap benchmark measures
    against)."""

    name = "async"
    supported_schedules = SCHEDULES

    def __init__(self, mesh=None, *, reduction: str = "exact",
                 serialize: bool = False):
        import weakref
        self.mesh = mesh
        self.reduction = reduction
        self.serialize = serialize
        self._cache: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()

    def lowered(self, compiled, fetches=None,
                virtual_stages_per_device: int | None = None
                ) -> AsyncLoweredGraph:
        """The (cached) per-stage lowering for this plan + fetch list."""
        per_plan = self._cache.get(compiled)
        if per_plan is None:
            per_plan = self._cache[compiled] = {}
        v = compiled.virtual_stages_per_device \
            if virtual_stages_per_device is None \
            else virtual_stages_per_device
        key = (tuple(fetches) if fetches else None, v)
        lw = per_plan.get(key)
        if lw is None:
            lw = AsyncLoweredGraph(
                compiled.graph, compiled.strategy_index,
                shape_env=compiled.shape_env, mesh=self.mesh,
                topology=compiled.topology, reduction=self.reduction,
                fetches=list(fetches) if fetches else None,
                virtual_stages_per_device=v)
            per_plan[key] = lw
        lw.serialize = self.serialize
        return lw

    def run(self, compiled, state, fetches=None
            ) -> dict[str, ShardedTensor]:
        return self.lowered(compiled, fetches).run(state)

    def run_schedule(self, compiled, schedule: PipelineSchedule, states,
                     fetches=None) -> list[dict[str, ShardedTensor]]:
        if schedule.kind not in self.supported_schedules:
            raise ScheduleError(
                f"executor {self.name!r} does not support schedule kind "
                f"{schedule.kind!r}; supported kinds are "
                f"{', '.join(repr(s) for s in self.supported_schedules)}")
        if len(states) != schedule.num_microbatches:
            raise ScheduleError(
                f"{len(states)} microbatch states for a "
                f"{schedule.num_microbatches}-microbatch schedule")
        if schedule.n_stages != compiled.n_stages:
            raise ScheduleError(
                f"schedule has {schedule.n_stages} stage(s) but the plan "
                f"has {compiled.n_stages}")
        lw = self.lowered(compiled, fetches,
                          virtual_stages_per_device=schedule.
                          virtual_per_stage)
        return lw.run_schedule(schedule, list(states))
