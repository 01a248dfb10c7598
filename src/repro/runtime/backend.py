"""Execution backend: run resolved communication plans on real JAX devices.

Public surface:

* :func:`execute_plan` — run a :class:`CommPlan` over per-device numpy
  shards and return the destination shards (all data actually moves
  through XLA collectives under ``jax.shard_map``),
* :func:`execute_sharded` — the same, adapted to the simulator's
  :class:`~repro.core.simulator.ShardedTensor` (drop-in replacement for
  ``simulator.apply_plan``),
* :func:`resharding_fn` — resolve (src, dst) once and return a reusable
  migration function, caching the compiled program per global shape,
* :func:`device_items` — the per-device :class:`ExecItem` view of a plan
  (what progressive specialization hands each device; paper §5.3).
"""

from __future__ import annotations

import numpy as np

from repro.core.annotations import HSPMD
from repro.core.plan import CommPlan, box_shape
from repro.core.simulator import ShardedTensor
from repro.core.specialize import ExecItem
from repro.core.topology import Topology

from .lowering import (DeviceOrder, LoweringStats, lower_plan, pack_shards,
                       pad_shape)


def _default_mesh(n: int):
    from repro.launch.mesh import make_runtime_mesh
    return make_runtime_mesh(n)


class CompiledPlan:
    """A plan lowered once for a (mesh, shape, reduction); reusable over
    fresh shard values without retracing."""

    def __init__(self, plan: CommPlan, shape: tuple[int, ...], mesh, *,
                 reduction: str = "exact"):
        if plan.src is None:
            raise ValueError("plan has no source annotation")
        self.plan = plan
        self.shape = tuple(shape)
        self.mesh = mesh
        self.order = DeviceOrder.for_plan(plan)
        self.n_mesh = int(mesh.devices.size)
        if self.n_mesh < len(self.order):
            from repro.launch.mesh import device_shortfall
            raise ValueError(device_shortfall(
                "plan", len(self.order), self.n_mesh,
                mesh.devices.flat[0].platform))
        self.stats = LoweringStats()
        self.fn = lower_plan(plan, self.shape, mesh, self.order,
                             reduction=reduction, stats_out=self.stats)

    def _pack(self, parts: dict[int, np.ndarray]) -> np.ndarray:
        return pack_shards(parts, self.plan.src, self.shape, self.n_mesh,
                           self.order)

    def _unpack(self, out: np.ndarray) -> dict[int, np.ndarray]:
        dst = self.plan.annots[-1]
        result: dict[int, np.ndarray] = {}
        for dev in dst.devices:
            bshape = box_shape(dst.device_box(dev, self.shape))
            result[dev] = out[(self.order.pos(dev),)
                              + tuple(slice(0, s) for s in bshape)].copy()
        return result

    def __call__(self, parts: dict[int, np.ndarray]) -> dict[int, np.ndarray]:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(self.mesh.axis_names[0], *([None] * len(self.shape)))
        inp = jax.device_put(self._pack(parts),
                             NamedSharding(self.mesh, spec))
        return self._unpack(np.asarray(self.fn(inp)))


def compile_plan(plan: CommPlan, shape: tuple[int, ...], mesh=None, *,
                 reduction: str = "exact") -> CompiledPlan:
    mesh = mesh or _default_mesh(len(DeviceOrder.for_plan(plan)))
    return CompiledPlan(plan, shape, mesh, reduction=reduction)


def execute_plan(plan: CommPlan, parts: dict[int, np.ndarray],
                 shape: tuple[int, ...], mesh=None, *,
                 reduction: str = "exact") -> dict[int, np.ndarray]:
    """Execute ``plan`` on real devices; ``parts`` maps each source device
    to its local shard (shaped by ``plan.src.device_box``)."""
    return compile_plan(plan, shape, mesh, reduction=reduction)(parts)


def execute_sharded(st: ShardedTensor, plan: CommPlan, mesh=None, *,
                    reduction: str = "exact") -> ShardedTensor:
    """``simulator.apply_plan`` signature-compatible real-device execution."""
    parts = execute_plan(plan, st.parts, st.shape, mesh,
                         reduction=reduction)
    return ShardedTensor(st.shape, plan.annots[-1], parts)


def resharding_fn(src_annot: HSPMD, dst_annot: HSPMD, mesh=None, *,
                  topology: Topology | None = None,
                  reduction: str = "exact"):
    """Resolve (src, dst) and return ``fn(parts, shape) -> parts`` that
    migrates shards on real devices; the plan AND its lowered shard_map
    program are cached per global shape (repeat migrations don't
    retrace)."""
    from repro.core.comm_resolve import resolve

    plans: dict[tuple[int, ...], CompiledPlan] = {}

    def fn(parts: dict[int, np.ndarray],
           shape: tuple[int, ...]) -> dict[int, np.ndarray]:
        shape = tuple(int(s) for s in shape)
        compiled = plans.get(shape)
        if compiled is None:
            plan = resolve(src_annot, dst_annot, shape, topology)
            compiled = plans[shape] = compile_plan(plan, shape, mesh,
                                                   reduction=reduction)
        return compiled(parts)

    fn.plans = plans
    return fn


def execute_graph(graph, strategy: int = 0, *, state=None, mesh=None,
                  shape_env=None, topology=None, reduction: str = "exact",
                  fetches=None) -> dict[str, ShardedTensor]:
    """Execute a deduced graph's compute AND comm ExecItems end-to-end on
    real devices under one ``shard_map`` program (see ``runtime.program``).

    ``state`` maps every leaf tensor name (placeholders + parameters) to
    its :class:`ShardedTensor`; returns the fetched tensors (default: the
    graph's sinks) as ShardedTensors under their deduced annotations.
    """
    from .program import lower_graph
    lowered = lower_graph(graph, strategy, shape_env=shape_env, mesh=mesh,
                          topology=topology, reduction=reduction,
                          fetches=fetches)
    return lowered.run(state or {})


def device_items(plan: CommPlan, device: int, name: str = "comm") -> list[ExecItem]:
    """The ExecItems ``device`` executes for this plan — identical filtering
    to :func:`repro.core.specialize.specialize`'s CommOp substitution."""
    items = []
    for stage in plan.stages:
        for step in stage.steps:
            mine = [g for g in step.groups
                    if device in g.srcs or device in g.dsts]
            if mine or (step.kind in ("ID", "Slice")
                        and device in stage.annot_after.devices):
                items.append(ExecItem(step.kind, name, "comm",
                                      f"{len(mine)} group(s)"))
    return items
