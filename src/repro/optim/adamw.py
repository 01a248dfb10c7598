"""AdamW with optional ZeRO-style sharded optimizer states.

The optimizer state pytree mirrors the parameter pytree, so under pjit the
states inherit the parameters' HSPMD-derived shardings (FSDP over the
``data`` axis x TP over ``model``) — the storage equivalent of ZeRO-3,
with the ZeRO-1 variant (states sharded, params replicated) selectable by
the sharding rules.  The paper's elastic scenarios (§7.2) disable
optimizer-state sharding for restart-free fault tolerance; that maps here
to passing fully-replicated state specs.

Both the pytree update (:func:`apply_updates`) and the update over
sharded weights (:func:`sharded_apply_updates`) apply one elementwise
step, :func:`adamw_leaf`, in float32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100


def init_opt_state(params):
    return {
        "m": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params),
        "v": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params),
        "count": jnp.zeros((), jnp.int32),
    }


def _schedule(cfg: AdamWConfig, count):
    warm = jnp.minimum(count.astype(jnp.float32) / max(cfg.warmup_steps, 1),
                       1.0)
    return cfg.lr * warm


def adamw_leaf(p, g, m, v, scale, lr, bc1, bc2, b1, omb1, b2, omb2, eps,
               wd):
    """One leaf's AdamW step in float32 on the clipped gradient
    ``g * scale``: bias-corrected moments and decoupled weight decay.
    Returns ``(new p, new m, new v)``; ``p`` keeps its dtype."""
    g = g.astype(jnp.float32) * scale
    m = b1 * m + omb1 * g
    v = b2 * v + omb2 * g * g
    step = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v


def apply_updates(params, grads, opt_state, cfg: AdamWConfig):
    """Returns (new_params, new_opt_state, metrics)."""
    count = opt_state["count"] + 1

    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    c = count.astype(jnp.float32)
    bc1 = 1 - cfg.b1 ** c
    bc2 = 1 - cfg.b2 ** c
    lr = _schedule(cfg, count)

    out = jax.tree.map(
        lambda p, g, m_, v_: adamw_leaf(
            p, g, m_, v_, scale, lr, bc1, bc2, cfg.b1, 1 - cfg.b1, cfg.b2,
            1 - cfg.b2, cfg.eps, cfg.weight_decay),
        params, grads, opt_state["m"], opt_state["v"])
    new_params, m, v = jax.tree.transpose(
        jax.tree.structure(params), jax.tree.structure((0, 0, 0)), out)
    return new_params, {"m": m, "v": v, "count": count}, \
        {"grad_norm": gnorm, "lr": lr}


# ---------------------------------------------------------------------------
# sharded AdamW over ShardedTensors (repro.api Session.train_step)
# ---------------------------------------------------------------------------
#
# The graph-IR training step produces gradients as per-device
# ShardedTensors whose annotations MATCH the parameters' (backward's
# grad-reduce comm guarantees it), so the update is elementwise per
# shard.  Every path runs it as the same two jitted programs over the
# runtime's stacked ``(n_mesh, *pad)`` blocks, whose row ``p`` holds
# the shard of mesh position ``p``:
#
# * ``_row_squares``: each row's sum of squared gradient, per leaf;
# * ``_row_update``: the global norm from those sums, each tile once,
#   then :func:`adamw_leaf` on the row.
#
# Blocks on a device mesh (the JaxExecutor's unpipelined step) run each
# program once over the mesh, with m/v resident there.  Host shards
# (every other path) run them one row at a time on a one-device CPU
# mesh.  A row sees the same ``(1, *pad)`` program either way, so the
# two agree bit for bit: a numpy copy of the step would not, since XLA
# contracts its multiply-adds.  The norm sums the per-tile sums in one
# order fixed by the annotations (leaves by name, tiles by device),
# whichever rows hold them.

@functools.lru_cache(maxsize=64)
def block_layout(order, n_mesh: int, mesh, items: tuple) -> "BlockLayout":
    """The (shared) :class:`BlockLayout` of ``items``, a tuple of
    ``(name, annotation, shape)``."""
    return BlockLayout(order, n_mesh, mesh, items)


class BlockLayout:
    """Where named sharded tensors sit in stacked ``(n_mesh, *pad)``
    blocks: row ``order.pos(dev)`` holds device ``dev``'s shard at the
    origin, zero-padded to the elementwise-max shard shape
    (``runtime.lowering.pack_shards``).  ``mesh`` is the 1-D device mesh
    the blocks live on, None for host blocks.

    ``rows``/``cols`` pick, leaf by leaf (``cols`` indexes ``names``),
    one row holding each distinct tile; ``bounds`` maps the index of
    each leaf whose shards differ in shape to its rows' shard shapes;
    ``partial`` names the leaves whose shards are summands, which have
    no tiles."""

    def __init__(self, order, n_mesh: int, mesh, items: tuple):
        from repro.core.annotations import DUP
        from repro.runtime.lowering import pad_shape

        self.order, self.n_mesh, self.mesh = order, n_mesh, mesh
        self.annots = {n: a for n, a, _ in items}
        self.shapes = {n: tuple(s) for n, _, s in items}
        self.names = sorted(self.annots)
        self.pads = {n: pad_shape(self.annots[n], self.shapes[n])
                     for n in self.names}
        self.partial = [n for n in self.names if self.annots[n].has_partial]
        rows, cols = [], []
        self.bounds: dict[int, np.ndarray] = {}
        for i, name in enumerate(self.names):
            annot, shape = self.annots[name], self.shapes[name]
            if annot.has_partial:
                continue
            # subgroups copied across (hdim Duplicate) each cover the
            # whole tensor, however they split it: count the first alone
            seen = set()
            for dev in (annot.dgs[0].devices if annot.hdim == DUP
                        else annot.devices):
                box = annot.device_box(dev, shape)
                if box not in seen:
                    seen.add(box)
                    rows.append(order.pos(dev))
                    cols.append(i)
            local = {dev: tuple(annot.device_shape(dev, shape))
                     for dev in annot.devices}
            if any(s != self.pads[name] for s in local.values()):
                b = np.zeros((n_mesh, len(shape)), np.int32)
                for dev, s in local.items():
                    b[order.pos(dev)] = s
                self.bounds[i] = b
        self.rows = np.array(rows, np.int32)
        self.cols = np.array(cols, np.int32)
        self._zeros = None

    def pack(self, sts: dict) -> list[np.ndarray]:
        """Host blocks of ``{name: ShardedTensor}``, in ``names`` order."""
        from repro.runtime.lowering import pack_shards

        return [pack_shards(sts[n].parts, self.annots[n], self.shapes[n],
                            self.n_mesh, self.order) for n in self.names]

    def unpack(self, name: str, rows) -> "ShardedTensor":
        """A ShardedTensor of views into ``rows`` (row ``p``: mesh
        position ``p``'s padded shard)."""
        from repro.core.simulator import ShardedTensor

        annot, shape = self.annots[name], self.shapes[name]
        return ShardedTensor(shape, annot, {
            dev: rows[self.order.pos(dev)][
                tuple(slice(0, s) for s in annot.device_shape(dev, shape))]
            for dev in annot.devices})

    def _sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.mesh.axis_names[0]))

    def put(self, sts: dict) -> "Blocks":
        """Place ``{name: ShardedTensor}`` host shards on the mesh."""
        return Blocks(self, zip(self.names, jax.device_put(
            self.pack(sts), self._sharding())))

    def zeros(self) -> "Blocks":
        """float32 zero blocks made on the mesh (one program, compiled
        on first use)."""
        if self._zeros is None:
            shapes = [(self.n_mesh,) + self.pads[n] for n in self.names]

            def _zero_blocks():
                return tuple(jnp.zeros(s, jnp.float32) for s in shapes)

            self._zeros = jax.jit(_zero_blocks,
                                  out_shardings=self._sharding())
        return Blocks(self, zip(self.names, self._zeros()))


class Blocks(dict):
    """``{name: stacked (n_mesh, *pad) array}`` on a device mesh, placed
    as ``layout`` says."""

    def __init__(self, layout: BlockLayout, arrays=()):
        super().__init__(arrays)
        self.layout = layout

    def host(self) -> dict:
        """``{name: ShardedTensor}`` host views of the blocks."""
        from repro.runtime.program import fetch_rows

        names = list(self)
        rows = fetch_rows([self[n] for n in names], self.layout.n_mesh)
        return {n: self.layout.unpack(n, r) for n, r in zip(names, rows)}


def _row_squares(gs, bounds):
    """Per leaf, the sum of the row's squared gradient; padding (outside
    ``bounds``, where a leaf's shards differ in shape) counts zero."""
    out = []
    for i, g in enumerate(gs):
        g = g.astype(jnp.float32)
        b = bounds.get(i)
        if b is not None:
            inside = True
            for d in range(1, g.ndim):
                inside = inside & (jax.lax.broadcasted_iota(
                    jnp.int32, g.shape, d) < b[0, d - 1])
            g = jnp.where(inside, g, 0.0)
        out.append(jnp.sum(g * g))
    return jnp.stack(out)[None]


#: order of the scalars :func:`_row_update` takes in ``hyper``
HYPER = ("lr", "bc1", "bc2", "b1", "omb1", "b2", "omb2", "eps", "wd",
         "clip", "extra")


def _row_update(hyper, sq, rows, cols, ps, gs, ms, vs):
    """The global gradient norm from every row's ``sq`` (one entry per
    tile, plus ``extra``, the squares of leaves without tiles), the clip
    scale, and :func:`adamw_leaf` on each of the row's leaves."""
    h = dict(zip(HYPER, (hyper[i] for i in range(len(HYPER)))))
    gnorm = jnp.sqrt(jnp.sum(sq[rows, cols]) + h["extra"])
    scale = jnp.minimum(jnp.float32(1),
                        h["clip"] / (gnorm + jnp.float32(1e-9)))
    out = [adamw_leaf(p, g, m, v, scale, h["lr"], h["bc1"], h["bc2"],
                      h["b1"], h["omb1"], h["b2"], h["omb2"], h["eps"],
                      h["wd"])
           for p, g, m, v in zip(ps, gs, ms, vs)]
    return (tuple(o[0] for o in out), tuple(o[1] for o in out),
            tuple(o[2] for o in out), gnorm)


@functools.lru_cache(maxsize=16)
def _programs(mesh):
    """The two jitted programs over ``mesh``'s rows; the update donates
    m and v."""
    from jax.sharding import PartitionSpec as P

    row, whole = P(mesh.axis_names[0]), P()
    squares = jax.jit(jax.shard_map(
        _row_squares, mesh=mesh, in_specs=(row, row), out_specs=row,
        check_vma=False))
    update = jax.jit(jax.shard_map(
        _row_update, mesh=mesh,
        in_specs=(whole, whole, whole, whole, row, row, row, row),
        out_specs=(row, row, row, whole), check_vma=False),
        donate_argnums=(6, 7))
    return squares, update


@functools.lru_cache(maxsize=1)
def _cpu_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices("cpu")[:1]), ("rows",))


def _hyper(cfg: AdamWConfig, count: int) -> np.ndarray:
    c = np.float32(count)
    b1, b2 = np.float32(cfg.b1), np.float32(cfg.b2)
    warm = min(float(count) / max(cfg.warmup_steps, 1), 1.0)
    vals = {"lr": cfg.lr * warm, "bc1": 1 - b1 ** c, "bc2": 1 - b2 ** c,
            "b1": b1, "omb1": 1 - cfg.b1, "b2": b2, "omb2": 1 - cfg.b2,
            "eps": cfg.eps, "wd": cfg.weight_decay, "clip": cfg.grad_clip,
            "extra": 0.0}
    return np.array([vals[k] for k in HYPER], np.float32)


def _host_layout(params: dict) -> BlockLayout:
    from repro.runtime.lowering import DeviceOrder

    order = DeviceOrder(tuple(sorted(
        {d for st in params.values() for d in st.annot.devices})))
    items = tuple((n, params[n].annot, tuple(params[n].shape))
                  for n in sorted(params))
    return block_layout(order, len(order), None, items)


def init_sharded_state(params):
    """Zero optimizer state mirroring ``params``: float32 m/v shards
    under the SAME annotations (ZeRO-3 storage when the params are
    sharded, ZeRO-1 when only the states are), as host shards for
    ``{name: ShardedTensor}`` and as blocks on the mesh for
    :class:`Blocks`."""
    if isinstance(params, Blocks):
        lay = params.layout
        return {"m": lay.zeros(), "v": lay.zeros(), "count": 0}

    from repro.core.simulator import ShardedTensor

    def zeros_like(st):
        return ShardedTensor(
            st.shape, st.annot,
            {d: np.zeros(a.shape, np.float32)
             for d, a in st.parts.items()})

    return {"m": {n: zeros_like(st) for n, st in params.items()},
            "v": {n: zeros_like(st) for n, st in params.items()},
            "count": 0}


def sharded_apply_updates(params, grads, opt_state, cfg: AdamWConfig):
    """AdamW over sharded weights: ``(new_params, new_state, metrics)``,
    each in the form it was given.

    ``params`` and ``grads`` are either host shards,
    ``{name: ShardedTensor}`` with ``opt_state`` as
    :func:`init_sharded_state` makes it, or :class:`Blocks` on one mesh
    with m and v Blocks in ``opt_state``.  Blocks stay on the device and
    the update has finished on return; the given m and v blocks are
    donated to the new ones, ``params`` stay valid.  The step's
    telemetry record counts the leaves updated on the device and on the
    host, and gauges the optimizer state the device holds."""
    from repro.runtime import telemetry

    if set(params) != set(grads):
        raise ValueError(
            f"gradient names {sorted(grads)} do not match parameters "
            f"{sorted(params)}")
    count = opt_state["count"] + 1
    hyper = _hyper(cfg, count)
    if isinstance(params, Blocks):
        lay = params.layout
        squares, update = _programs(lay.mesh)
        gs = tuple(grads[n] for n in lay.names)
        new = update(hyper, squares(gs, lay.bounds), lay.rows,
                     lay.cols, tuple(params[n] for n in lay.names), gs,
                     tuple(opt_state["m"][n] for n in lay.names),
                     tuple(opt_state["v"][n] for n in lay.names))
        new_p, new_m, new_v = (Blocks(lay, zip(lay.names, x))
                               for x in jax.block_until_ready(new[:3]))
        gnorm = float(new[3])
        telemetry.count("opt_device_leaves", len(lay.names))
        telemetry.gauge("device_state_bytes", sum(
            a.nbytes for a in (*new_m.values(), *new_v.values())))
    else:
        new_p, new_m, new_v, gnorm = _host_update(params, grads, opt_state,
                                                  hyper)
        telemetry.count("opt_host_leaves", len(params))
        telemetry.gauge("device_state_bytes", 0)
    metrics = {"grad_norm": gnorm, "lr": float(hyper[HYPER.index("lr")])}
    return new_p, {"m": new_m, "v": new_v, "count": count}, metrics


def _host_update(params, grads, opt_state, hyper):
    """The two programs row by row on the CPU over host shards."""
    from repro.core.simulator import gather

    lay = _host_layout(params)
    ps, gs, ms, vs = (lay.pack(x) for x in
                      (params, grads, opt_state["m"], opt_state["v"]))
    hyper = hyper.copy()
    for n in lay.partial:       # summands: square the value they sum to
        g = np.asarray(gather(grads[n], check_dups=False), np.float32)
        hyper[HYPER.index("extra")] += np.sum(np.square(g), dtype=np.float32)
    cpu = jax.devices("cpu")[0]
    squares, update = _programs(_cpu_mesh())

    def row(blocks, r):
        return tuple(jax.device_put([b[r:r + 1] for b in blocks], cpu))

    sq = np.concatenate([np.asarray(squares(
        row(gs, r), {i: jax.device_put(b[r:r + 1], cpu)
                     for i, b in lay.bounds.items()}))
        for r in range(lay.n_mesh)])
    head = jax.device_put((hyper, sq, lay.rows, lay.cols), cpu)
    outs = [jax.device_get(update(*head, row(ps, r), row(gs, r), row(ms, r),
                                  row(vs, r)))
            for r in range(lay.n_mesh)]
    new = tuple({n: lay.unpack(n, [o[k][i][0] for o in outs])
                 for i, n in enumerate(lay.names)} for k in range(3))
    return new + (float(outs[0][3]),)
