"""Lower a :class:`~repro.core.plan.CommPlan` onto real JAX devices.

The simulator executes plans on a ``dict[device, np.ndarray]``; this module
compiles the *same* stage semantics into one ``jax.shard_map`` program over
a 1-D device mesh, so every resolved communication operator actually moves
tensors through XLA collectives:

* copy groups (SR / AG / SplitAG / BSR) — point-to-point deliveries are
  **fused into batched permutes**: all (src, dst) pairs of a stage are
  packed into rounds (each source and each destination used at most once
  per round) and every round becomes ONE ``jax.lax.ppermute`` over
  padded slabs, instead of one collective launch per pair.  The static
  round schedule is reported in :class:`LoweringStats`,
* reduce groups (AR / RS / SplitAR / SplitRS) — run as **subgroup
  collectives** via ``axis_index_groups`` whenever every destination is
  a source (non-participant mesh positions ride along as dummy partition
  entries; see ``PlanLowering._reduce_groups_static``), falling back to
  the masked full-axis form otherwise:
  - ``reduction="exact"``: ``jax.lax.all_gather`` of the per-source
    contributions, then a left fold in float64 following the group's
    ``srcs`` order.  This reproduces ``simulator.apply_plan`` **bit
    exactly** for arbitrary inputs (the simulator accumulates in float64
    in the same order before casting back),
  - ``reduction="fast"``: a single ``jax.lax.psum`` in the native
    dtype (a real all-reduce; bit-exact only when the data makes the sum
    order-insensitive, e.g. integer-valued shards),
* ID / Slice — no collective; covered by the local-retention path.

Per-device specialization (paper §5.3) is realized literally: the stage
state update is a ``jax.lax.switch`` over ``axis_index`` whose branches are
the per-device programs — each branch only writes the slice-group
deliveries that device participates in, mirroring
:func:`repro.core.specialize.specialize`.

Because every device can hold a differently-shaped box (heterogeneous
``hsplits``), local shards are padded to the per-stage elementwise-max box
shape; geometry is static, so stage coverage is checked at lowering time
with the same strictness as the simulator.

:class:`PlanLowering` is the reusable core: it applies one plan's stages
to a device-local padded value *inside an enclosing shard_map body*, so
the whole-graph executor (``runtime.program``) can interleave comm plans
with per-device compute.  :func:`lower_plan` wraps it into a standalone
jitted program.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.core.annotations import HSPMD
from repro.core.plan import (Box, CommPlan, box_contains, box_intersect,
                             box_shape, rel_slices)

REDUCTIONS = ("exact", "fast")


@dataclass(frozen=True)
class DeviceOrder:
    """Mapping between logical HSPMD device ids and mesh axis positions."""

    devices: tuple[int, ...]

    @classmethod
    def for_plan(cls, plan: CommPlan) -> "DeviceOrder":
        devs = set()
        if plan.src is not None:
            devs |= set(plan.src.devices)
        for annot in plan.annots:
            devs |= set(annot.devices)
        for step in plan.steps:
            for g in step.groups:
                devs |= set(g.srcs) | set(g.dsts)
        return cls(tuple(sorted(devs)))

    def pos(self, dev: int) -> int:
        return self.devices.index(dev)

    def __len__(self) -> int:
        return len(self.devices)


@dataclass
class LoweringStats:
    """Static collective-launch accounting of one lowered plan, plus the
    kernel-dispatch tallies of the compute seam (``runtime.program``):
    how many per-device attention ExecItems lowered onto the Pallas
    flash kernel vs the pure-XLA reference (``kernels.policy``)."""

    copy_pairs: int = 0      # point-to-point (src, dst) deliveries
    ppermute_calls: int = 0  # batched permutes emitted after fusion
    reduce_groups: int = 0   # all_gather / psum launches
    grouped_reduces: int = 0  # of which run on axis_index_groups subgroups
    uniform_reduce_stages: int = 0  # stages emitted switch-free + fused
    uniform_copy_stages: int = 0    # ident/gather stages emitted switch-free
    stages: int = 0
    ref_dispatches: int = 0     # attention classes on the XLA reference
    pallas_dispatches: int = 0  # attention classes on the Pallas kernels
    # specialization-class emission accounting (core.lowered_ir):
    compute_segments: int = 0       # live compute segments emitted
    straightline_segments: int = 0  # of which needed ZERO switches
    switch_branches_emitted: int = 0  # total class (+idle) branches

    def merge(self, other: "LoweringStats") -> None:
        self.copy_pairs += other.copy_pairs
        self.ppermute_calls += other.ppermute_calls
        self.reduce_groups += other.reduce_groups
        self.grouped_reduces += other.grouped_reduces
        self.uniform_reduce_stages += other.uniform_reduce_stages
        self.uniform_copy_stages += other.uniform_copy_stages
        self.stages += other.stages
        self.ref_dispatches += other.ref_dispatches
        self.pallas_dispatches += other.pallas_dispatches
        self.compute_segments += other.compute_segments
        self.straightline_segments += other.straightline_segments
        self.switch_branches_emitted += other.switch_branches_emitted


def pack_shards(parts, annot: HSPMD, shape: tuple[int, ...], n_mesh: int,
                order: DeviceOrder, out: "np.ndarray | None" = None
                ) -> np.ndarray:
    """Stack per-device shards into the runtime's ``(n_mesh, *pad)``
    buffer (each device's box zero-padded at the origin), validating
    every shard's shape against the annotation and promoting dtypes.

    ``out`` may pass a buffer from a PREVIOUS pack of the same tensor
    to fill in place (skips the zeroed allocation; the padding region
    is never written, so it stays zero from the first pack).  It is
    used only when its shape and dtype still match."""
    dtype = None
    for dev in annot.devices:
        arr = np.asarray(parts[dev])
        want = annot.device_shape(dev, shape)
        if tuple(arr.shape) != tuple(want):
            raise ValueError(
                f"dev {dev}: shard shape {arr.shape} != {want} "
                f"expected by the annotation")
        dtype = arr.dtype if dtype is None else \
            np.promote_types(dtype, arr.dtype)
    full = (n_mesh,) + pad_shape(annot, shape)
    if out is not None and out.shape == full and out.dtype == dtype:
        stacked = out
    else:
        stacked = np.zeros(full, dtype=dtype)
    for dev in annot.devices:
        arr = np.asarray(parts[dev])
        stacked[(order.pos(dev),)
                + tuple(slice(0, s) for s in arr.shape)] = arr
    return stacked


def pad_shape(annot: HSPMD, shape: tuple[int, ...]) -> tuple[int, ...]:
    """Elementwise max of the per-device box shapes (uniform local buffer)."""
    dims = [1] * len(shape)
    for dev in annot.devices:
        for d, s in enumerate(annot.device_shape(dev, shape)):
            dims[d] = max(dims[d], s)
    return tuple(dims)


def check_stage_coverage(prev: HSPMD, nxt: HSPMD,
                         deliveries: list[tuple[Box, tuple[int, ...]]],
                         shape: tuple[int, ...], kinds: str) -> None:
    """Static replica of the simulator's strict coverage assertion."""
    for dev in nxt.devices:
        box = nxt.device_box(dev, shape)
        covered = np.zeros(box_shape(box), dtype=bool)
        if dev in prev.devices:
            inter = box_intersect(prev.device_box(dev, shape), box)
            if inter is not None:
                covered[rel_slices(box, inter)] = True
        for dbox, dsts in deliveries:
            if dev not in dsts:
                continue
            inter = box_intersect(dbox, box)
            if inter is not None:
                covered[rel_slices(box, inter)] = True
        if not covered.all():
            raise AssertionError(
                f"dev {dev}: {int((~covered).sum())} uncovered elements "
                f"after stage [{kinds}]")


@dataclass
class _Round:
    """One batched permute: (src, dst) pairs with distinct srcs and dsts."""

    pairs: list[tuple[int, int, object]] = field(default_factory=list)
    srcs: set[int] = field(default_factory=set)
    dsts: set[int] = field(default_factory=set)

    def add(self, s: int, d: int, g) -> None:
        self.pairs.append((s, d, g))
        self.srcs.add(s)
        self.dsts.add(d)


def _fuse_rounds(pairs: list[tuple[int, int, object]]) -> list[_Round]:
    """Greedy round construction: each round uses every source and every
    destination at most once (ppermute's partial-permutation contract)."""
    rounds: list[_Round] = []
    for s, d, g in pairs:
        for r in rounds:
            if s not in r.srcs and d not in r.dsts:
                r.add(s, d, g)
                break
        else:
            r = _Round()
            r.add(s, d, g)
            rounds.append(r)
    return rounds


class PlanLowering:
    """Applies one CommPlan's stages to a device-local padded value inside
    an enclosing ``shard_map`` body.

    All geometry (boxes, fusion rounds, coverage) is computed and checked
    statically at construction; :meth:`apply` only emits traced ops.
    """

    def __init__(self, plan: CommPlan, shape: tuple[int, ...],
                 order: DeviceOrder, axis: str, n_mesh: int, *,
                 reduction: str = "exact", fuse_permutes: bool = True):
        if reduction not in REDUCTIONS:
            raise ValueError(f"reduction must be one of {REDUCTIONS}")
        if plan.src is None:
            raise ValueError("plan has no source annotation")
        if n_mesh < len(order):
            from repro.launch.mesh import device_shortfall
            raise ValueError(device_shortfall("plan", len(order), n_mesh))
        self.plan = plan
        self.shape = tuple(shape)
        self.order = order
        self.axis = axis
        self.n_mesh = n_mesh
        self.reduction = reduction
        # fuse_permutes=False is the GSPMD-resharding baseline for the
        # overlap micro-benchmark: every (src, dst) copy becomes its own
        # single-pair ppermute round and the uniform switch-free fast
        # paths are disabled, so each delivery is a separate collective
        # launch (same bits, more launches)
        self.fuse_permutes = fuse_permutes
        self.stats = LoweringStats()
        self.has_reduce = any(g.reduce for s in plan.steps for g in s.groups)
        # set while walking the groups below: exact mode only needs the
        # float64 fold machinery for groups of MORE than two sources (a
        # two-operand group's exact-fold-then-cast IS the native-dtype
        # psum bitwise) or groups that cannot run on a psum subgroup
        self.needs_x64 = False

        # static geometry per stage, verified up front; copy deliveries
        # fused into batched-permute rounds, reduce groups mapped onto
        # axis_index_groups subgroup collectives where possible
        self._stage_rounds: list[list[_Round]] = []
        self._reduce_partitions: dict[int, tuple] = {}
        self._uniform_stages: list[dict | None] = []
        prev = plan.src
        for stage in plan.stages:
            uni = (self._uniform_stage_static(stage, prev)
                   or self._uniform_ident_static(stage, prev)
                   or self._uniform_gather_static(stage, prev)) \
                if fuse_permutes else None
            self._uniform_stages.append(uni)
            if uni is not None:
                if uni["kind"] == "reduce":
                    self.stats.uniform_reduce_stages += 1
                else:
                    self.stats.uniform_copy_stages += 1
            deliveries = [(g.box, g.dsts) for step in stage.steps
                          for g in step.groups]
            pairs = []
            for step in stage.steps:
                for g in step.groups:
                    for s in g.srcs:
                        sbox = prev.device_box(s, self.shape)
                        if not box_contains(sbox, g.box):
                            raise AssertionError(
                                f"src dev {s} box {sbox} does not contain "
                                f"group box {g.box}")
                    if g.reduce:
                        self.stats.reduce_groups += 1
                        part = self._reduce_groups_static(g)
                        self._reduce_partitions[id(g)] = part
                        if part[0 if reduction == "fast" else 1]:
                            self.stats.grouped_reduces += 1
                        if len(g.srcs) > 2 or part[0] is None:
                            self.needs_x64 = True
                        continue
                    src = g.srcs[0]
                    for d in g.dsts:
                        if d != src:
                            pairs.append((src, d, g))
            kinds = "+".join(st.kind for st in stage.steps)
            check_stage_coverage(prev, stage.annot_after, deliveries,
                                 self.shape, kinds)
            if fuse_permutes:
                rounds = _fuse_rounds(pairs)
            else:               # GSPMD-style: one ppermute per pair
                rounds = []
                for s, d, g in pairs:
                    r = _Round()
                    r.add(s, d, g)
                    rounds.append(r)
            self._stage_rounds.append(rounds)
            if uni is None:    # uniform stages never emit the rounds
                self.stats.copy_pairs += len(pairs)
                self.stats.ppermute_calls += len(rounds)
            self.stats.stages += 1
            prev = stage.annot_after

    def _uniform_stage_static(self, stage, prev) -> dict | None:
        """Static descriptor of a *uniform reduce stage* — the symmetric
        case where every mesh position plays the identical role, so the
        stage lowers switch-free with ONE fused collective:

        * every group is a reduce whose destinations equal its sources,
        * the groups' source positions partition the whole mesh axis
          into equal-size subgroups,
        * every source extracts the same slice of its local padded
          buffer (regular tilings make the extract position-invariant
          in *local* coordinates even though the global boxes differ),
        * every destination's next-annotation box is fully covered by
          its group's box, at the same local offsets.

        This is the comm-side analogue of the compute segments' single
        specialization class: per-device ``lax.switch`` emission (and
        one collective per group) collapses to straight-line code with
        a single ``axis_index_groups`` collective for all groups.
        Returns ``None`` when any condition fails (masked per-group
        emission is kept as the general path)."""
        groups = [g for step in stage.steps for g in step.groups]
        if not groups or not all(g.reduce for g in groups):
            return None
        if any(set(g.dsts) != set(g.srcs) for g in groups):
            return None
        k = len(groups[0].srcs)
        if any(len(g.srcs) != k for g in groups):
            return None
        pos_groups = [[self.order.pos(s) for s in g.srcs] for g in groups]
        flat = sorted(p for ps in pos_groups for p in ps)
        if flat != list(range(self.n_mesh)):
            return None
        gshape = box_shape(groups[0].box)
        src_rel = None
        for g in groups:
            if box_shape(g.box) != gshape:
                return None
            for s in g.srcs:
                r = rel_slices(prev.device_box(s, self.shape), g.box)
                if src_rel is None:
                    src_rel = r
                elif r != src_rel:
                    return None
        nxt = stage.annot_after
        if set(nxt.devices) != set(self.order.devices):
            return None
        dst_rel = piece_rel = nbox_shape = None
        for g in groups:
            for dev in g.dsts:
                nbox = nxt.device_box(dev, self.shape)
                inter = box_intersect(g.box, nbox)
                if inter != nbox:   # piece must fully cover the dst box
                    return None
                d_r = rel_slices(nbox, inter)
                p_r = rel_slices(g.box, inter)
                bs = box_shape(nbox)
                if dst_rel is None:
                    dst_rel, piece_rel, nbox_shape = d_r, p_r, bs
                elif (d_r, p_r, bs) != (dst_rel, piece_rel, nbox_shape):
                    return None
        return {"kind": "reduce", "src_rel": src_rel, "groups": pos_groups,
                "k": k, "dst_rel": dst_rel, "piece_rel": piece_rel,
                "next_pad": pad_shape(nxt, self.shape)}

    @staticmethod
    def _has_partial(annot) -> bool:
        from repro.core.annotations import PARTIAL
        return annot.hdim == PARTIAL or \
            any(ds.has_partial for ds in annot.dss)

    def _uniform_ident_static(self, stage, prev) -> dict | None:
        """Static descriptor of a *uniform identity stage* — no
        deliveries at all: every device re-slices data it already
        holds, with the same local output shape everywhere.  Only the
        slice OFFSETS vary per mesh position (DP slab selection, TP
        column selection), so per-device ``lax.switch`` emission
        collapses to one ``dynamic_slice`` driven by a position-indexed
        offset table — zero branches, zero collectives.  Excludes
        Partial layouts: a Partial shard is a summand, and re-slicing
        summands is only meaningful through a reduce stage."""
        if any(step.groups for step in stage.steps):
            return None
        if len(self.order) != self.n_mesh:
            return None
        nxt = stage.annot_after
        if set(nxt.devices) != set(self.order.devices):
            return None
        if not set(self.order.devices) <= set(prev.devices):
            return None
        if self._has_partial(prev) or self._has_partial(nxt):
            return None
        out_shape = None
        starts: list = [None] * self.n_mesh
        for dev in self.order.devices:
            pbox = prev.device_box(dev, self.shape)
            nbox = nxt.device_box(dev, self.shape)
            if box_intersect(pbox, nbox) != nbox:
                return None      # output not locally available
            bs = box_shape(nbox)
            if out_shape is None:
                out_shape = bs
            elif bs != out_shape:
                return None
            r = rel_slices(pbox, nbox)
            starts[self.order.pos(dev)] = tuple(s.start for s in r)
        if out_shape != pad_shape(nxt, self.shape):
            return None
        return {"kind": "ident", "starts": starts, "out_shape": out_shape}

    def _uniform_gather_static(self, stage, prev) -> dict | None:
        """Static descriptor of a *uniform gather stage*: pure copy
        deliveries where every device contributes its (identical-shape)
        local shard and assembles its next box from ``k`` such pieces
        at identical destination offsets — only WHICH positions supply
        the pieces differs.  Lowers to a single full-axis
        ``all_gather`` plus a position-indexed piece table: no
        switches, no permute rounds.  Copies are exact, so the path is
        valid under either reduction mode; sources with overlapping
        boxes are interchangeable because replicated shards are bitwise
        identical (Partial layouts, whose shards are summands, are
        excluded)."""
        groups = [g for step in stage.steps for g in step.groups]
        if not groups or any(g.reduce for g in groups):
            return None
        if len(self.order) != self.n_mesh:
            return None
        nxt = stage.annot_after
        if set(nxt.devices) != set(self.order.devices):
            return None
        if set(prev.devices) != set(self.order.devices):
            return None
        if self._has_partial(prev) or self._has_partial(nxt):
            return None
        pboxes = [prev.device_box(self.order.devices[p], self.shape)
                  for p in range(self.n_mesh)]
        piece_shape = box_shape(pboxes[0])
        if any(box_shape(b) != piece_shape for b in pboxes):
            return None
        if piece_shape != pad_shape(prev, self.shape):
            return None
        next_pad = pad_shape(nxt, self.shape)
        template: list | None = None   # (dst_rel, piece_rel, shape) per tile
        picks: list = [None] * self.n_mesh
        for dev in self.order.devices:
            nbox = nxt.device_box(dev, self.shape)
            if box_shape(nbox) != next_pad:
                return None
            tiles, seen = [], set()
            for p in range(self.n_mesh):
                ib = box_intersect(pboxes[p], nbox)
                if ib is not None and ib not in seen:
                    seen.add(ib)
                    tiles.append(ib)
            tiles.sort(key=lambda b: tuple(lo for lo, _ in b))
            if sum(int(np.prod(box_shape(t))) for t in tiles) != \
                    int(np.prod(next_pad)):
                return None      # tiles must cover the dst box exactly...
            for a in range(len(tiles)):
                for b in range(a + 1, len(tiles)):
                    if box_intersect(tiles[a], tiles[b]) is not None:
                        return None   # ...without overlap
            if template is None:
                template = []
                for t in tiles:
                    p = next((p for p in range(self.n_mesh)
                              if box_contains(pboxes[p], t)), None)
                    if p is None:
                        return None
                    template.append((rel_slices(nbox, t),
                                     rel_slices(pboxes[p], t),
                                     box_shape(t)))
            if len(tiles) != len(template):
                return None
            chosen = []
            for t, (d_r, p_r, ts) in zip(tiles, template):
                if rel_slices(nbox, t) != d_r or box_shape(t) != ts:
                    return None
                p = next((p for p in range(self.n_mesh)
                          if box_contains(pboxes[p], t)
                          and rel_slices(pboxes[p], t) == p_r), None)
                if p is None:
                    return None
                chosen.append(p)
            picks[self.order.pos(dev)] = chosen
        return {"kind": "gather", "piece_shape": piece_shape,
                "k": len(template),
                "dst_rel": [t[0] for t in template],
                "piece_rel": [t[1] for t in template],
                "picks": picks, "next_pad": next_pad}

    def _emit_uniform_ident(self, x, uni, i, out_dtype):
        import jax
        import jax.numpy as jnp

        if all(not any(s) for s in uni["starts"]) and \
                tuple(x.shape) == tuple(uni["out_shape"]):
            return x.astype(out_dtype)      # pure no-op stage
        st = jnp.asarray(uni["starts"], jnp.int32)[i]
        y = jax.lax.dynamic_slice(
            x, tuple(st[d] for d in range(len(uni["out_shape"]))),
            uni["out_shape"])
        return y.astype(out_dtype)

    def _emit_uniform_gather(self, x, uni, i, out_dtype):
        import jax
        import jax.numpy as jnp

        contrib = x[tuple(slice(0, n) for n in uni["piece_shape"])]
        gathered = jax.lax.all_gather(contrib, self.axis)
        picks = jnp.asarray(uni["picks"], jnp.int32)[i]
        arr = jnp.zeros(uni["next_pad"], out_dtype)
        for t in range(uni["k"]):
            piece = gathered[picks[t]]
            arr = arr.at[uni["dst_rel"][t]].set(
                piece[uni["piece_rel"][t]].astype(out_dtype))
        return arr

    def _emit_uniform_stage(self, x, uni, out_dtype, i=None):
        """Straight-line emission of a uniform stage: reduce stages get
        one fused subgroup collective, ident/gather stages a
        position-indexed slice / full-axis gather — never a switch.
        Exact mode folds reduces in float64; for subgroups of <=2
        sources a float64 ``psum`` IS the ordered fold bitwise
        (two-operand IEEE addition is commutative), so the all_gather +
        sequential fold is only kept for larger groups."""
        import jax
        import jax.numpy as jnp

        if uni["kind"] == "ident":
            return self._emit_uniform_ident(x, uni, i, out_dtype)
        if uni["kind"] == "gather":
            return self._emit_uniform_gather(x, uni, i, out_dtype)
        contrib = x[uni["src_rel"]]
        if self.reduction == "fast" or uni["k"] <= 2:
            # exact for k<=2 without float64: the exact sum of two
            # values fits in float64, so the ordered f64 fold cast back
            # to the input dtype is the correctly-rounded two-operand
            # sum — i.e. bitwise the native-dtype psum
            if self.reduction != "fast":
                assert jnp.dtype(out_dtype) == contrib.dtype, \
                    "two-operand psum shortcut needs matching dtypes"
            y = jax.lax.psum(contrib, self.axis,
                             axis_index_groups=uni["groups"])
        else:
            gathered = jax.lax.all_gather(
                contrib.astype(jnp.float64), self.axis,
                axis_index_groups=uni["groups"])
            y = gathered[0]
            for j in range(1, uni["k"]):
                y = y + gathered[j]
        arr = jnp.zeros(uni["next_pad"], out_dtype)
        return arr.at[uni["dst_rel"]].set(
            y[uni["piece_rel"]].astype(out_dtype))

    def _reduce_groups_static(self, g) -> tuple[list | None, list | None]:
        """axis_index_groups partitions for one reduce group: the
        ``(psum_groups, all_gather_groups)`` pair, either of which is
        ``None`` when the masked full-axis collective must be kept.

        The source devices form one subgroup; every other mesh position
        still has to appear (XLA requires a partition of the axis), so
        non-participants ride along as singletons for psum (ragged
        partitions are fine for all-reduce) and as equal-size dummy
        chunks for all_gather (gather output shapes must be uniform —
        when the remainder doesn't chunk evenly the exact path falls
        back to the full axis).  Results on non-source devices are
        garbage, which is only safe because every destination is a
        source; otherwise both stay masked full-axis.
        """
        pos = [self.order.pos(s) for s in g.srcs]  # srcs order == fold order
        if not set(g.dsts) <= set(g.srcs):
            return None, None
        others = [p for p in range(self.n_mesh) if p not in set(pos)]
        psum_groups = [pos] + [[p] for p in others]
        k = len(pos)
        ag_groups = None
        if len(others) % k == 0:
            ag_groups = [pos] + [others[i:i + k]
                                 for i in range(0, len(others), k)]
        return psum_groups, ag_groups

    # -- traced emission ---------------------------------------------------

    def _emit_rounds(self, x, rounds: list[_Round], prev_annot, i):
        """Emit the stage's fused permutes; returns, per copy group, the
        received piece expression valid on each destination device."""
        import jax
        import jax.numpy as jnp

        received: dict[tuple[int, int], object] = {}  # (dst, id(g)) -> arr
        for r in rounds:
            pad = tuple(max(box_shape(g.box)[d] for _, _, g in r.pairs)
                        for d in range(len(self.shape)))
            operand = jnp.zeros(pad, x.dtype)
            for s, _, g in r.pairs:  # each src appears once per round
                sl = rel_slices(prev_annot.device_box(s, self.shape), g.box)
                val = jnp.zeros(pad, x.dtype).at[
                    tuple(slice(0, n) for n in box_shape(g.box))].set(x[sl])
                operand = jnp.where(i == self.order.pos(s), val, operand)
            perm = [(self.order.pos(s), self.order.pos(d))
                    for s, d, _ in r.pairs]
            out = jax.lax.ppermute(operand, self.axis, perm)
            for _, d, g in r.pairs:
                received[(d, id(g))] = out[
                    tuple(slice(0, n) for n in box_shape(g.box))]
        return received

    def _emit_copy_piece(self, x, g, prev_annot, i, received):
        import jax.numpy as jnp

        src = g.srcs[0]
        bshape = box_shape(g.box)
        piece = jnp.zeros(bshape, x.dtype)
        for d in g.dsts:
            if d == src:
                val = x[rel_slices(prev_annot.device_box(src, self.shape),
                                   g.box)]
            else:
                val = received[(d, id(g))]
            piece = jnp.where(i == self.order.pos(d), val, piece)
        return piece

    def _emit_reduce(self, x, g, prev_annot, i):
        import jax
        import jax.numpy as jnp

        # per-source contribution: each source extracts its own slice of
        # the group box (offsets differ per source), everyone else is zero
        branch_of_pos = [0] * self.n_mesh
        extracts = [None]
        for s in g.srcs:
            branch_of_pos[self.order.pos(s)] = len(extracts)
            extracts.append(rel_slices(
                prev_annot.device_box(s, self.shape), g.box))
        gshape = box_shape(g.box)
        branches = [lambda v: jnp.zeros(gshape, v.dtype)]
        for sl in extracts[1:]:
            branches.append(lambda v, sl=sl: v[sl])
        tbl = jnp.asarray(branch_of_pos, jnp.int32)
        contrib = jax.lax.switch(tbl[i], branches, x)
        psum_groups, ag_groups = self._reduce_partitions[id(g)]
        if self.reduction == "fast":
            return jax.lax.psum(contrib, self.axis,
                                axis_index_groups=psum_groups)
        if psum_groups is not None and len(g.srcs) <= 2:
            # native-dtype psum == the ordered f64 fold cast back,
            # bitwise, for <=2 sources (two-operand addition is
            # commutative and its exact sum fits in float64)
            return jax.lax.psum(contrib, self.axis,
                                axis_index_groups=psum_groups)
        if ag_groups is not None:
            # subgroup gather: position j within the group IS g.srcs[j],
            # so the float64 fold keeps the simulator's srcs order
            gathered = jax.lax.all_gather(contrib.astype(jnp.float64),
                                          self.axis,
                                          axis_index_groups=ag_groups)
            acc = gathered[0]
            for j in range(1, len(g.srcs)):
                acc = acc + gathered[j]
            return acc
        gathered = jax.lax.all_gather(contrib.astype(jnp.float64), self.axis)
        acc = gathered[self.order.pos(g.srcs[0])]
        for s in g.srcs[1:]:
            acc = acc + gathered[self.order.pos(s)]
        return acc

    def _stage_update(self, x, pieces, prev_annot, next_annot, i, out_dtype):
        import jax
        import jax.numpy as jnp

        next_pad = pad_shape(next_annot, self.shape)

        def branch_for(pos):
            if pos >= len(self.order) or \
                    self.order.devices[pos] not in next_annot.devices:
                return lambda v: jnp.zeros(next_pad, out_dtype)
            dev = self.order.devices[pos]
            nbox = next_annot.device_box(dev, self.shape)

            def build(v):
                arr = jnp.zeros(next_pad, out_dtype)
                if dev in prev_annot.devices:
                    pbox = prev_annot.device_box(dev, self.shape)
                    inter = box_intersect(pbox, nbox)
                    if inter is not None:
                        arr = arr.at[rel_slices(nbox, inter)].set(
                            v[rel_slices(pbox, inter)].astype(out_dtype))
                for dbox, piece, dsts in pieces:
                    if dev not in dsts:
                        continue
                    inter = box_intersect(dbox, nbox)
                    if inter is None:
                        continue
                    arr = arr.at[rel_slices(nbox, inter)].set(
                        piece[rel_slices(dbox, inter)].astype(out_dtype))
                return arr

            return build

        return jax.lax.switch(i, [branch_for(p) for p in range(self.n_mesh)],
                              x)

    def apply(self, x, i, out_dtype=None):
        """Run the plan's stages on local padded value ``x`` (this device's
        shard at the origin); ``i`` is the traced mesh axis index."""
        out_dtype = out_dtype or x.dtype
        prev_annot = self.plan.src
        for stage, rounds, uni in zip(self.plan.stages, self._stage_rounds,
                                      self._uniform_stages):
            if uni is not None:
                x = self._emit_uniform_stage(x, uni, out_dtype, i)
                prev_annot = stage.annot_after
                continue
            received = self._emit_rounds(x, rounds, prev_annot, i)
            pieces = []
            for step in stage.steps:
                for g in step.groups:
                    if g.reduce:
                        piece = self._emit_reduce(x, g, prev_annot, i)
                    else:
                        piece = self._emit_copy_piece(x, g, prev_annot, i,
                                                      received)
                    pieces.append((g.box, piece, g.dsts))
            x = self._stage_update(x, pieces, prev_annot, stage.annot_after,
                                   i, out_dtype)
            prev_annot = stage.annot_after
        return x


def maybe_x64(fn, needs_x64: bool):
    """Wrap ``fn`` in a thread-local x64 scope when the exact float64 fold
    is traced (keyed into the jit cache; process defaults untouched)."""
    if not needs_x64:
        return fn
    import jax

    def run_x64(*args):
        with jax.enable_x64(True):
            return fn(*args)

    return run_x64


def lower_plan(plan: CommPlan, shape: tuple[int, ...], mesh,
               order: DeviceOrder | None = None, *,
               reduction: str = "exact", dtype=None,
               stats_out: LoweringStats | None = None,
               fuse_permutes: bool = True):
    """Compile ``plan`` into a jitted ``f(stacked) -> stacked`` over ``mesh``.

    ``stacked`` has shape ``(mesh_size, *pad_shape(plan.src))``: row
    ``order.pos(dev)`` holds device ``dev``'s (zero-padded) local shard.
    The result is stacked the same way under the final stage annotation.
    ``fuse_permutes=False`` lowers copies GSPMD-resharding style — one
    ppermute per (src, dst) pair, uniform fast paths off — the baseline
    the batched-permute fusion micro-benchmark measures against.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    order = order or DeviceOrder.for_plan(plan)
    axis = mesh.axis_names[0]
    n_mesh = int(mesh.devices.size)
    lowering = PlanLowering(plan, shape, order, axis, n_mesh,
                            reduction=reduction,
                            fuse_permutes=fuse_permutes)
    if stats_out is not None:
        stats_out.merge(lowering.stats)

    def body(block):
        x = block[0]
        i = jax.lax.axis_index(axis)
        return lowering.apply(x, i, dtype or x.dtype)[None]

    rank = len(shape)
    spec = P(axis, *([None] * rank))
    jitted = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                   out_specs=spec, check_vma=False))
    return maybe_x64(jitted, lowering.needs_x64 and reduction == "exact")
