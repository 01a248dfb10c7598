"""Whole-graph execution on real devices: compute + comm ExecItems.

``runtime.lowering`` executes a single CommPlan; this module lowers an
entire deduced :class:`~repro.core.graph.Graph` — every compute op AND
every resolved CommOp — into ONE ``jax.shard_map`` program over a 1-D
device mesh, so a progressively-specialized pipeline stage runs
end-to-end on real devices (paper §5.3-5.4):

* each tensor that crosses a communication boundary lives as a stacked
  ``(mesh, *padded_local)`` buffer whose row ``order.pos(dev)`` holds
  device ``dev``'s local shard at the origin (heterogeneous ``hsplits``
  boxes are zero-padded to the per-tensor elementwise-max box shape),
* compute ops are lowered through the **specialization-class IR**
  (``core.lowered_ir``): maximal runs of compute ops between comm ops
  form segments, and each segment emits ONE branch per *class* of
  devices sharing the identical local program — in the common
  homogeneous SPMD case (one class, every device) the whole segment is
  straight-line unpadded code with zero switches; heterogeneous or
  pipeline-staged segments get a small ``jax.lax.switch`` over classes
  (never over devices), with a zero branch only when some mesh position
  idles through the segment (non-local operator removal, executed
  literally),
* a CommOp applies its resolved plan's stages via
  :class:`~repro.runtime.lowering.PlanLowering` (fused batched permutes,
  exact or fast reductions) on the same buffers.

The per-device programs are exactly the ExecItem lists progressive
specialization produces (``core.specialize.specialize``) — the class
partition is their quotient, checked against them by
``core.lowered_ir.check_against_exec_items`` — and the
SimulatorExecutor interprets the same classes with vectorized numpy,
which is what the differential tests compare against.

Joint fwd+bwd TRAINING graphs (``Program.compile_train``) lower through
the very same path: backward ops are ordinary graph ops (autodiff VJP
kernels share ``local_apply`` with the simulator), activation-grad and
grad-reduce CommOps are resolved plans like any other, and the scanned
microbatch axis carries the per-microbatch gradient summands — so one
shard_map program realizes the whole fwd → bwd → grad-reduce step that
the SimulatorExecutor executes as explicit fwd/bwd timetable ticks
(bit-exact parity checked by the ``api:train/*`` selftest cases).
"""

from __future__ import annotations

import numpy as np

from repro.core.graph import Graph
from repro.core.lowered_ir import (CommSlot, Segment, partition_graph)
from repro.core.op_semantics import local_apply, result_dtype
from repro.core.simulator import ShardedTensor
from repro.core.specialize import resolve_comm_ops
from repro.core.symbolic import bind_shape
from repro.core.topology import Topology
from repro.kernels.policy import select_attention_impl_per_class

from . import telemetry
from .lowering import (DeviceOrder, LoweringStats, PlanLowering, maybe_x64,
                       pack_shards, pad_shape)


# ---------------------------------------------------------------------------
# shared emission helpers (whole-graph AND per-stage lowerings)
#
# LoweredGraph (one scanned program) and runtime.async_program (one
# program per virtual pipeline stage) trace the SAME per-class segment
# code through these functions, which is what keeps the two backends
# bitwise interchangeable: a segment's class branches, dtype chain and
# pad/unpad slicing are one definition, not two.
# ---------------------------------------------------------------------------

def segment_liveness(graph: Graph, segments, fetches
                     ) -> dict[int, tuple[list[str], list[str]]]:
    """``id(segment) -> (live_in, live_out)``: values produced AND
    consumed inside one segment stay unpadded inside its branches; only
    live-outs (consumed by ops outside the segment, or fetched)
    materialize as stacked ``(mesh, *pad)`` buffers."""
    consumers: dict[str, set[int]] = {}
    for op in graph.ops:
        for t in op.inputs:
            consumers.setdefault(t.name, set()).add(id(op))
    fetch_set = set(fetches)
    out: dict[int, tuple[list[str], list[str]]] = {}
    for seg in segments:
        seg_ids = {id(op) for op in seg.ops}
        produced: list[str] = [op.outputs[0].name for op in seg.ops]
        produced_set = set(produced)
        live_in: list[str] = []
        for op in seg.ops:
            for t in op.inputs:
                if t.name not in produced_set and t.name not in live_in:
                    live_in.append(t.name)
        live_out = [n for n in produced
                    if n in fetch_set
                    or (consumers.get(n, set()) - seg_ids)]
        out[id(seg)] = (live_in, live_out)
    return out


def run_segment_class(seg, cls, dtypes, live_in, live_out, out_pads, vs,
                      platform: str):
    """Trace one class's local program over the segment: slice live-ins
    to the class's exact local shapes once, keep every interior value
    unpadded, re-pad only the live-outs.  ``platform`` is that of the
    mesh the program is placed on: Pallas kernels compile for ``"tpu"``
    and run through the interpreter anywhere else."""
    import jax.numpy as jnp

    local = dict(zip(live_in, vs))
    exact: dict[str, object] = {}
    for op, spec in zip(seg.ops, cls.specs):
        if spec is None:
            continue        # this class does not run the op
        ins = []
        for t, shp in zip(op.inputs, spec.in_shapes):
            v = exact.get(t.name)
            if v is None:
                v = local[t.name]
                if tuple(v.shape) != tuple(shp):
                    v = v[tuple(slice(0, s) for s in shp)]
            ins.append(v)
        name = op.outputs[0].name
        if spec.impl == "pallas":
            from repro.kernels.flash_attention import flash_attention
            y = flash_attention(*ins, causal=op.attrs.get("causal", True),
                                interpret=platform != "tpu")
        else:
            y = local_apply(op.kind, jnp, ins, op.attrs, spec.out_shape)
        exact[name] = y.astype(dtypes[name])
    outs = []
    for name in live_out:
        pad = out_pads[name]
        y = exact.get(name)
        if y is None:
            outs.append(jnp.zeros(pad, dtypes[name]))
        elif tuple(y.shape) == pad:
            outs.append(y)
        else:
            outs.append(jnp.zeros(pad, dtypes[name]).at[
                tuple(slice(0, s) for s in y.shape)].set(y))
    return tuple(outs)


def emit_segment(seg, tenv, i, *, seg_live, graph: Graph, k: int,
                 shapes, order: DeviceOrder, n_mesh: int,
                 platform: str) -> None:
    """Emit one compute segment into the traced env ``tenv``: one branch
    per specialization class (straight-line when homogeneous over the
    whole mesh), plus a zero branch when some mesh position idles."""
    import jax
    import jax.numpy as jnp

    live_in, live_out = seg_live[id(seg)]
    if not live_out:
        return              # dead code: nothing escapes
    # shared dtype chain (class-independent: promotion depends only on
    # input dtypes, identical across classes)
    dtypes: dict[str, np.dtype] = {}
    for op in seg.ops:
        dtypes[op.outputs[0].name] = result_dtype(
            op.kind,
            [dtypes.get(t.name, None)
             or np.dtype(tenv[t.name].dtype)
             for t in op.inputs])
    out_pads = {
        n: pad_shape(graph.tensors[n].annots[k], shapes[n])
        for n in live_out}
    args = [tenv[n] for n in live_in]
    n_cls = seg.n_classes
    pos_cls = []
    for p in range(n_mesh):
        c = seg.class_of(order.devices[p]) if p < len(order) else None
        pos_cls.append(n_cls if c is None else c)
    if n_cls == 1 and all(c == 0 for c in pos_cls):
        outs = run_segment_class(seg, seg.classes[0], dtypes, live_in,
                                 live_out, out_pads, args, platform)
    else:
        branches = [
            (lambda cls: lambda *vs: run_segment_class(
                seg, cls, dtypes, live_in, live_out, out_pads, vs,
                platform))(cls)
            for cls in seg.classes]
        if any(c == n_cls for c in pos_cls):
            branches.append(lambda *vs: tuple(
                jnp.zeros(out_pads[n], dtypes[n]) for n in live_out))
        tbl = jnp.asarray(pos_cls, jnp.int32)
        outs = jax.lax.switch(tbl[i], branches, *args)
    for name, y in zip(live_out, outs):
        tenv[name] = y


def fetch_rows(outs, n_mesh: int) -> list:
    """Per-mesh-position host rows for each fetched device array.

    On the CPU backend each per-device shard is host memory already, so
    ``np.from_dlpack`` views it without the stitch-and-copy that
    ``jax.device_get`` performs on a sharded array (the DLPack capsule
    keeps the jax buffer alive for as long as the views are).  On an
    accelerator the shards live in device memory, and one bulk
    ``device_get`` copies them all to the host."""
    import jax

    if not outs or outs[0].addressable_shards[0].device.platform != "cpu":
        return [[arr[i] for i in range(n_mesh)]
                for arr in jax.device_get(outs)]
    per_out = []
    for out in outs:
        rows: list = [None] * n_mesh
        for sh in out.addressable_shards:
            idx = sh.index[0]
            pos = (idx.start or 0) if isinstance(idx, slice) else int(idx)
            rows[pos] = np.from_dlpack(sh.data)[0]
        per_out.append(rows)
    return per_out


def unpack_rows(graph: Graph, k: int, shapes, order: DeviceOrder,
                name: str, rows: list) -> ShardedTensor:
    """Stacked host rows -> ShardedTensor under ``name``'s annotation
    (parts are views into the rows; callers never mutate shards in
    place)."""
    annot = graph.tensors[name].annots[k]
    shape = shapes[name]
    parts = {
        dev: rows[order.pos(dev)][
            tuple(slice(0, s) for s in annot.device_shape(dev, shape))]
        for dev in annot.devices}
    return ShardedTensor(shape, annot, parts)


class LoweredGraph:
    """A deduced graph + strategy compiled to one shard_map program,
    reusable over fresh shard values without retracing.

    With ``num_microbatches=m > 1`` the SAME program additionally scans
    over a leading microbatch axis: placeholder buffers carry all ``m``
    microbatch shards stacked at axis 1, a ``jax.lax.scan`` runs the
    per-device body (unchanged segment emissions + comm lowerings)
    once per microbatch, and every fetch comes back per-microbatch — the
    pipeline schedule's work, expressed as one XLA program whose
    dependence order realizes the same 1F1B/GPipe overlap.  The graph
    passed in must then be the MICRO graph (shapes already scaled;
    ``Program.compile_micro``).

    Interleaved virtual stages (Megatron's ``v`` chunks per device;
    ``schedule.infer_virtual_stages``) need no special lowering: a
    device holding ``v`` chunks simply belongs to the participant class
    of every one of its chunks' segments, and the wrap-around CommOps
    route activations around the device ring ``v`` times inside the same
    scanned body.  ``n_virtual_stages`` surfaces the deduced chunk
    structure (``n_stages * v``) for introspection — the explicit
    interleaved timetable remains the SimulatorExecutor's contract,
    checked bit-exactly against this program by the
    ``api:pipeline/interleaved*`` selftest cases."""

    def __init__(self, graph: Graph, strategy: int = 0, *,
                 shape_env: dict[str, int] | None = None, mesh=None,
                 topology: Topology | None = None,
                 reduction: str = "exact", fetches=None,
                 num_microbatches: int = 1):
        import jax
        from jax.sharding import PartitionSpec as P

        self.graph = graph
        self.k = strategy
        if num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be >= 1 (got {num_microbatches})")
        self.num_microbatches = num_microbatches
        env = shape_env or {}
        self.shapes = {name: bind_shape(t.shape, env)
                       for name, t in graph.tensors.items()}
        resolved = resolve_comm_ops(graph, strategy, topology, shape_env)
        self._plans = {id(rc.op): rc.plan for rc in resolved}
        # id -> op, built ONCE (plan lowering below used to re-scan
        # graph.comm_ops per plan — an O(n^2) linear hunt)
        self._comm_op_by_id = {id(op): op for op in graph.comm_ops}
        # kept for the lazy pipeline/chunk introspection properties
        self._resolved_comms = resolved
        self._pipelines: "list | None" = None
        self._pack_bufs: dict[str, np.ndarray] = {}

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
        # the placed devices, not the process default, pick the kernel
        platform = mesh.devices.flat[0].platform
        if self.n_mesh < len(self.order):
            from repro.launch.mesh import device_shortfall
            raise ValueError(device_shortfall(
                "graph", len(self.order), self.n_mesh, platform))
        axis = mesh.axis_names[0]

        self.leaves = [o.outputs[0] for o in graph.ops
                       if o.kind in ("placeholder", "parameter")]
        self.fetches = list(fetches or [t.name for t in graph.sinks()])
        for f in self.fetches:
            if f not in graph.tensors:
                raise ValueError(f"unknown fetch tensor {f!r}")

        self.stats = LoweringStats()
        lowerings: dict[int, PlanLowering] = {}
        needs_x64 = False
        for oid, op in self._comm_op_by_id.items():
            plan = self._plans[oid]
            shape = self.shapes[op.inputs[0].name]
            pl = PlanLowering(plan, shape, self.order, axis, self.n_mesh,
                              reduction=reduction)
            lowerings[oid] = pl
            self.stats.merge(pl.stats)
            needs_x64 |= pl.needs_x64

        # Kernel dispatch is decided STATICALLY, per specialization
        # class, from the device-LOCAL shard shapes — a TP-split head
        # dim can make a shard kernel-eligible (or not) independent of
        # the global shape.  Devices whose shard shapes agree share ONE
        # decision (kernels.policy memoizes per distinct shape pair),
        # and the decision participates in the class partition: same
        # shapes but different impls would be different classes.
        k, shapes = strategy, self.shapes

        def impl_of(op, dev):
            if op.kind != "attention":
                return ""
            qs = shapes[op.inputs[0].name]
            ks = shapes[op.inputs[1].name]
            return select_attention_impl_per_class(
                tuple(op.inputs[0].annots[k].device_shape(dev, qs)),
                tuple(op.inputs[1].annots[k].device_shape(dev, ks)),
                platform)

        self.ir = partition_graph(graph, strategy, shapes=shapes,
                                  impl_of=impl_of,
                                  devices=self.order.devices)

        # static per-segment liveness (shared helper; also used by the
        # per-stage async lowering)
        self._seg_live = segment_liveness(graph, self.ir.segments,
                                          self.fetches)

        # branch accounting: the structural win the benchmark records.
        # A homogeneous segment (one class, every mesh position) is
        # straight-line — zero switches; anything else emits one branch
        # per class (+ one zero branch when some position idles).
        extra_idle = self.n_mesh > len(self.order)
        for seg in self.ir.segments:
            if not self._seg_live[id(seg)][1]:
                continue                    # dead segment: never emitted
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

        order, n_mesh = self.order, self.n_mesh
        seg_live = self._seg_live

        # placeholders carry a per-microbatch axis in microbatched mode;
        # parameters are microbatch-invariant and stay single-buffer
        self._per_mb = {t.name for t in self.leaves
                        if t.producer is not None
                        and t.producer.kind == "placeholder"}
        m = num_microbatches
        entries = self.ir.entries

        def eval_ops(tenv, i):
            import jax.numpy as jnp

            # single-stage uniform reduces (the grad-reduce common case)
            # are DEFERRED and batched: one fused multi-operand psum per
            # distinct group partition instead of one collective per
            # comm op — collectives on a host mesh are latency-bound,
            # so rendezvous count is what matters.  A deferred value is
            # flushed the moment a segment or comm op consumes it; the
            # fold order per group is unchanged, so results stay
            # bit-identical to one-at-a-time emission.
            deferred: dict[str, tuple] = {}

            def flush(names=None):
                todo = [(n, deferred.pop(n)) for n in
                        (list(deferred) if names is None else names)
                        if n in deferred]
                by_key: dict[tuple, list] = {}
                for name, item in todo:
                    pl, uni, x, od = item
                    # fast mode and two-source exact groups both run a
                    # native-dtype psum (for k<=2 it IS the f64 fold
                    # cast back, bitwise); only larger exact groups
                    # need the ordered float64 fold
                    path = "psum" if pl.reduction == "fast" \
                        or uni["k"] <= 2 else "fold"
                    key = (tuple(tuple(g) for g in uni["groups"]), path)
                    by_key.setdefault(key, []).append((name,) + item)
                for (gk, path), items in by_key.items():
                    if path == "fold":
                        for name, pl, uni, x, od in items:
                            tenv[name] = pl._emit_uniform_stage(x, uni,
                                                                od)
                        continue
                    contribs = [x[uni["src_rel"]]
                                for name, pl, uni, x, od in items]
                    # one flat buffer -> ONE all-reduce (a variadic
                    # psum is split back per operand by XLA); summing
                    # the concatenation is elementwise, so results are
                    # bitwise those of per-op collectives
                    dt = jnp.result_type(*(c.dtype for c in contribs))
                    flat = jnp.concatenate(
                        [c.astype(dt).ravel() for c in contribs]) \
                        if len(contribs) > 1 else contribs[0]
                    y_all = jax.lax.psum(
                        flat, axis,
                        axis_index_groups=[list(g) for g in gk])
                    off = 0
                    for (name, pl, uni, x, od), c in zip(items,
                                                         contribs):
                        if len(contribs) == 1:
                            y = y_all
                        else:
                            n = int(np.prod(c.shape)) if c.shape else 1
                            y = y_all[off:off + n].reshape(
                                c.shape).astype(c.dtype)
                            off += n
                        tenv[name] = jnp.zeros(uni["next_pad"], od).at[
                            uni["dst_rel"]].set(
                                y[uni["piece_rel"]].astype(od))

            for entry in entries:
                if isinstance(entry, CommSlot):
                    op = entry.op
                    in_name = op.inputs[0].name
                    if in_name in deferred:
                        flush([in_name])
                    x = tenv[in_name]
                    pl = lowerings[id(op)]
                    unis = pl._uniform_stages
                    if len(unis) == 1 and unis[0] is not None \
                            and unis[0]["kind"] == "reduce":
                        deferred[op.outputs[0].name] = \
                            (pl, unis[0], x, x.dtype)
                    else:
                        tenv[op.outputs[0].name] = pl.apply(x, i,
                                                            x.dtype)
                else:
                    live_in, _ = self._seg_live[id(entry)]
                    pend = [n for n in live_in if n in deferred]
                    if pend:
                        flush(pend)
                    emit_segment(entry, tenv, i, seg_live=seg_live,
                                 graph=graph, k=k, shapes=shapes,
                                 order=order, n_mesh=n_mesh,
                                 platform=platform)
            flush()
            return tenv

        def body(*blocks):
            i = jax.lax.axis_index(axis)
            if m == 1:
                tenv = {t.name: b[0] for t, b in zip(self.leaves, blocks)}
                tenv = eval_ops(tenv, i)
                return tuple(tenv[f][None] for f in self.fetches)
            shared = {t.name: b[0] for t, b in zip(self.leaves, blocks)
                      if t.name not in self._per_mb}
            xs = {t.name: b[0] for t, b in zip(self.leaves, blocks)
                  if t.name in self._per_mb}          # (m, *pad) each

            def mb_body(carry, x_j):
                tenv = eval_ops({**shared, **x_j}, i)
                return carry, tuple(tenv[f] for f in self.fetches)

            _, ys = jax.lax.scan(mb_body, 0, xs, length=m)  # ys (m, *pad)
            return tuple(y[None] for y in ys)

        def leaf_rank(t):
            rank = len(shapes[t.name])
            return rank + 1 if m > 1 and t.name in self._per_mb else rank

        in_specs = tuple(P(axis, *([None] * leaf_rank(t)))
                         for t in self.leaves)
        out_rank = {f: len(shapes[f]) + (1 if m > 1 else 0)
                    for f in self.fetches}
        out_specs = tuple(P(axis, *([None] * out_rank[f]))
                          for f in self.fetches)
        self._jitted = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))
        self._x64 = needs_x64 and reduction == "exact"
        self.fn = maybe_x64(self._jitted, self._x64)

    def lower(self, dtypes):
        """Ahead-of-time lowering of the step program from shapes alone:
        ``dtypes`` maps every leaf name to its dtype, and each argument
        becomes a ``ShapeDtypeStruct`` placed on the mesh.  No array is
        made, so the mesh may be a described device topology that is
        not attached.  ``.compile()`` on the result gives
        ``memory_analysis()`` and the compiled text.  The lowering is
        the one a call traces (same placement, same x64 scope), so in
        the same process a later call with arrays of these dtypes runs
        that executable without compiling again."""
        import jax

        args = []
        for t in self.leaves:
            mb = (self.num_microbatches,) \
                if self.num_microbatches > 1 and t.name in self._per_mb \
                else ()
            shape = (self.n_mesh,) + mb + pad_shape(
                t.annots[self.k], self.shapes[t.name])
            args.append(jax.ShapeDtypeStruct(
                shape, np.dtype(dtypes[t.name]),
                sharding=self._block_sharding(len(shape))))
        return maybe_x64(self._jitted.lower, self._x64)(*args)

    # -- introspection (lazy: not on the lowering/execution path) ----------

    @property
    def pipelines(self):
        """Deduced pipeline structure (shares the lowering's comm
        resolution); computed on first access."""
        if self._pipelines is None:
            from repro.core.specialize import construct_pipelines
            self._pipelines = construct_pipelines(
                self.graph, self.k, resolved_comms=self._resolved_comms)
        return self._pipelines

    @property
    def n_stages(self) -> int:
        return max((p.n_stages for p in self.pipelines), default=1)

    @property
    def n_virtual_stages(self) -> int:
        """Physical stages * interleave chunks (Megatron's ``S * v``)."""
        from repro.core.schedule import infer_virtual_stages
        return self.n_stages * infer_virtual_stages(
            self.graph, self.k, self.pipelines)

    # -- pack / unpack -----------------------------------------------------

    def _pack(self, st: ShardedTensor, annot, shape,
              buf_key: str | None = None) -> np.ndarray:
        # leaf blocks are re-packed every step with identical geometry;
        # keyed buffers skip the zeroed allocation (safe: device_put
        # copies into per-device buffers before the next pack runs)
        out = self._pack_bufs.get(buf_key) if buf_key else None
        stacked = pack_shards(st.parts, annot, shape, self.n_mesh,
                              self.order, out=out)
        if buf_key:
            self._pack_bufs[buf_key] = stacked
        return stacked

    def _put(self, stacked: np.ndarray):
        return self._put_all([stacked])[0]

    def _block_sharding(self, ndim: int):
        """Placement of a stacked leaf block: row ``p`` on mesh position
        ``p``, the rest of the block whole on that device."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.mesh.axis_names[0],
                                          *([None] * (ndim - 1))))

    def _put_all(self, blocks: list[np.ndarray]):
        """One batched ``device_put`` for all leaf blocks."""
        import jax

        return jax.device_put(
            blocks, [self._block_sharding(b.ndim) for b in blocks])

    def _unpack(self, name: str, rows: list) -> ShardedTensor:
        return unpack_rows(self.graph, self.k, self.shapes, self.order,
                           name, rows)

    def _fetch_rows(self, outs) -> list:
        return fetch_rows(outs, self.n_mesh)

    def run(self, state: dict[str, ShardedTensor], update=None
            ) -> dict[str, ShardedTensor]:
        """Execute once; ``state`` maps every leaf name (placeholder AND
        parameter) to its ShardedTensor under the strategy annotation.

        ``update``, when given, runs between the call and the fetch as
        ``update(placed, outs)``: both map names to device arrays, the
        placed leaf blocks and the step's outputs, and it returns the
        ``{name: array}`` to fetch instead of ``outs`` (each unpacked
        under its name's annotation)."""
        if self.num_microbatches != 1:
            raise ValueError("microbatched program: use run_microbatches")
        blocks = []
        with telemetry.span("feed.pack"):
            for t in self.leaves:
                if t.name not in state:
                    raise ValueError(f"missing leaf tensor {t.name!r}")
                annot = t.annots[self.k]
                blocks.append(self._pack(
                    state[t.name], annot, self.shapes[t.name],
                    buf_key=t.name))
            telemetry.hold(self._pack_bufs)
        return self._run_blocks(blocks, lambda fetched: {
            name: self._unpack(name, rows)
            for name, rows in fetched.items()}, update)

    def run_microbatches(self, states: list[dict[str, ShardedTensor]]
                         ) -> list[dict[str, ShardedTensor]]:
        """Execute the scanned program over ``num_microbatches`` leaf
        states (microbatch ``j``'s placeholders in ``states[j]``;
        parameters read from ``states[0]``).  Returns per-microbatch
        fetches, bit-comparable to ``SimulatorExecutor.run_schedule``."""
        m = self.num_microbatches
        if m == 1:
            raise ValueError("unpipelined program: use run")
        if len(states) != m:
            raise ValueError(
                f"{len(states)} microbatch states for a {m}-microbatch "
                f"program")
        blocks = []
        with telemetry.span("feed.pack"):
            for t in self.leaves:
                annot = t.annots[self.k]
                shape = self.shapes[t.name]
                if t.name in self._per_mb:
                    for st in states:
                        if t.name not in st:
                            raise ValueError(
                                f"missing leaf tensor {t.name!r}")
                    blocks.append(np.stack(
                        [self._pack(st[t.name], annot, shape,
                                    buf_key=f"{t.name}#{j}")
                         for j, st in enumerate(states)], axis=1))
                else:
                    if t.name not in states[0]:
                        raise ValueError(f"missing leaf tensor {t.name!r}")
                    blocks.append(self._pack(states[0][t.name], annot,
                                             shape, buf_key=t.name))
            telemetry.hold(self._pack_bufs)

        def unpack(fetched):
            results: list[dict[str, ShardedTensor]] = \
                [{} for _ in range(m)]
            for name, rows in fetched.items():
                for j in range(m):              # rows[pos] (m, *pad)
                    results[j][name] = self._unpack(
                        name, [r[j] for r in rows])
            return results

        return self._run_blocks(blocks, unpack)

    def _run_blocks(self, blocks: list[np.ndarray], unpack, update=None):
        """Place the packed leaf blocks, run the step program until its
        outputs are ready, let ``update`` (see :meth:`run`) replace
        them, and hand the fetched host rows, by name, to ``unpack``."""
        import jax

        with telemetry.span("feed.put"):
            placed = self._put_all(blocks)
        telemetry.count("h2d_bytes", sum(b.nbytes for b in blocks))
        with telemetry.span("call"):
            # the fetch would wait for the outputs anyway; waiting here
            # keeps that wait in the call's span and out of the fetch's
            outs = dict(zip(self.fetches,
                            jax.block_until_ready(self.fn(*placed))))
        if update is not None:
            outs = update({t.name: b for t, b in zip(self.leaves, placed)},
                          outs)
        # drop the step's device arrays as soon as they are used: held
        # to the end of the step they moved ~60 ms of host work a step
        # (1.68 GB each way, TPU v5e) from the next put to between steps
        del placed
        telemetry.count("d2h_bytes", sum(o.nbytes for o in outs.values()))
        with telemetry.span("fetch"):
            rows = self._fetch_rows(list(outs.values()))
            fetched = dict(zip(outs, rows))
            del outs
            return unpack(fetched)


def plan_input_name(graph: Graph, op_id: int) -> str:
    """Input tensor name of the CommOp with ``id(op) == op_id``.

    Kept for external callers; ``LoweredGraph`` itself builds the
    id -> op map once instead of re-scanning per plan."""
    by_id = {id(op): op for op in graph.comm_ops}
    try:
        return by_id[op_id].inputs[0].name
    except KeyError:
        raise KeyError(op_id) from None


def lower_graph(graph: Graph, strategy: int = 0, *,
                shape_env: dict[str, int] | None = None, mesh=None,
                topology: Topology | None = None, reduction: str = "exact",
                fetches=None, num_microbatches: int = 1) -> LoweredGraph:
    """Compile a deduced graph for one strategy; see :class:`LoweredGraph`."""
    return LoweredGraph(graph, strategy, shape_env=shape_env, mesh=mesh,
                        topology=topology, reduction=reduction,
                        fetches=fetches, num_microbatches=num_microbatches)
