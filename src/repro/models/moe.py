"""Mixture-of-Experts layer (GShard-style capacity dispatch).

Covers grok-1 (8 experts, top-2) and DeepSeek-V2 (2 shared + 160 routed,
top-6).  The dense dispatch/combine einsum formulation is used because it
shards cleanly under GSPMD: with the expert dim Split over the ``model``
mesh axis, XLA inserts the all-to-all the paper's expert parallelism
requires — which our HSPMD layer annotates and the roofline pass measures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import _init, apply_mlp, init_mlp


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"router": _init(ks[0], (d, m.n_experts), dtype)}
    # routed experts: stacked (E, d, ff) weights
    def one_expert(k):
        return init_mlp(k, d, m.d_expert, cfg.mlp, dtype)
    p["experts"] = jax.vmap(one_expert)(
        jax.random.split(ks[1], m.n_experts))
    if m.n_shared:
        p["shared"] = jax.vmap(lambda k: init_mlp(k, d, m.d_expert, cfg.mlp,
                                                  dtype))(
            jax.random.split(ks[2], m.n_shared))
    return p


def _capacity(tokens: int, m) -> int:
    if m.exact:
        return tokens  # every token fits any expert: no drops
    cap = int(tokens * m.top_k * m.capacity_factor / m.n_experts)
    # round UP to a 128 multiple: MXU-aligned expert matmuls AND keeps the
    # (E, cap, d) buffer divisible for GSPMD (an unaligned cap measurably
    # DEGRADES the partitioning — §Perf iteration 4, refuted-then-refined)
    cap = max(cap, 1)
    return ((cap + 127) // 128) * 128 if cap > 128 else cap


def apply_moe_ep_shmap(p, x, cfg: ModelConfig, mesh):
    """Expert-parallel MoE via shard_map (§Perf iteration 6).

    Observation: activations are replicated across the ``model`` axis
    (only batch is data-sharded), so no token all-to-all is needed at
    all — each (data, model) device processes ITS batch shard's tokens
    through ITS model-shard's experts, and one bf16 psum over ``model``
    combines the per-expert-shard partial outputs.  The GSPMD
    scatter/gather dispatch instead reshuffled multi-GB replicated
    buffers with AR/AG pairs (measured ~8 GB/layer/microbatch).

    Requires E % tp == 0; falls back to the GSPMD path otherwise.
    Drop policy: capacity is enforced per (batch shard x expert), a
    standard local-capacity variant (exact mode keeps zero drops).
    """
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    b, s, d = x.shape
    tp = mesh.shape["model"]
    bd = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    e_loc = m.n_experts // tp

    def local(xt, router, experts, shared):
        # xt: (T_loc, d); experts: (E_loc, d, f) — weights arrive full
        # (their FSDP 'data' dim is all-gathered by the caller spec)
        mi = jax.lax.axis_index("model")
        T_loc = xt.shape[0]
        logits = (xt @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        cap = _capacity(T_loc, m) if not m.exact else T_loc
        lo = mi * e_loc
        rel = top_e - lo                                   # (T,k)
        mine = (rel >= 0) & (rel < e_loc)
        A = T_loc * m.top_k
        flat_rel = jnp.where(mine, rel, e_loc).reshape(A)
        order = jnp.argsort(flat_rel, stable=True)
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[flat_rel].add(1)
        starts = jnp.cumsum(counts) - counts
        ranks = jnp.arange(A, dtype=jnp.int32) - starts[flat_rel[order]]
        pos = jnp.zeros((A,), jnp.int32).at[order].set(ranks)
        keep = mine.reshape(A) & (pos < cap)
        e_idx = jnp.where(keep, flat_rel, e_loc)
        p_idx = jnp.minimum(pos, cap - 1)

        buf = jnp.zeros((e_loc, cap, d), xt.dtype)
        buf = buf.at[e_idx, p_idx].add(
            jnp.repeat(xt, m.top_k, axis=0), mode="drop")
        out = jax.vmap(lambda w, h: apply_mlp(w, h, cfg.mlp))(experts, buf)
        flat_out = out.reshape(e_loc * cap, d)
        slot = jnp.minimum(e_idx, e_loc - 1) * cap + p_idx
        gathered = flat_out[slot].reshape(T_loc, m.top_k, d)
        w = (top_p * keep.reshape(T_loc, m.top_k)).astype(xt.dtype)
        y = jnp.einsum("tkd,tk->td", gathered, w)
        if m.n_shared:
            # shared experts: compute on model-rank 0's slice only? No —
            # replicate across ranks and divide by tp inside the psum
            sh = jax.vmap(lambda w_: apply_mlp(w_, xt, cfg.mlp))(shared)
            y = y + jnp.sum(sh, axis=0) / tp
        y = jax.lax.psum(y, "model")
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, m.n_experts), 1), 0)
        aux = m.router_aux_coef * m.n_experts * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, "model")
        for ax in bd:
            aux = jax.lax.pmean(aux, ax)
        return y, aux

    xt = x.reshape(b * s, d)
    import jax.tree_util as jtu
    experts_specs = jtu.tree_map(lambda _: P("model", None, None),
                                 p["experts"])
    shared_arg = p.get("shared") if m.n_shared else jnp.zeros(())
    shared_specs = (jtu.tree_map(lambda _: P(None, None, None), p["shared"])
                    if m.n_shared else P())
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(bd, None), P(None, None), experts_specs,
                                 shared_specs),
                       out_specs=(P(bd, None), P()), check_vma=False)
    y, aux = fn(xt, p["router"], p["experts"], shared_arg)
    return y.reshape(b, s, d).astype(x.dtype), aux


def apply_moe(p, x, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux_loss).

    Dispatches to the shard_map expert-parallel formulation when a
    production mesh is active and the expert count divides the TP degree
    (§Perf iteration 6); otherwise the GSPMD scatter/gather path below.
    """
    from repro.sharding.hints import _active_mesh
    mesh = _active_mesh()
    tokens = x.shape[0] * x.shape[1]
    if (mesh is not None and "model" in mesh.axis_names
            and cfg.moe.n_experts % mesh.shape["model"] == 0
            and tokens >= 4096  # tiny decode batches: expert-weight AG
                                # would dominate (measured regression)
            and tokens % max(
                int(np.prod([mesh.shape[a] for a in mesh.axis_names
                             if a in ("pod", "data")])), 1) == 0):
        return apply_moe_ep_shmap(p, x, cfg, mesh)
    return _apply_moe_gspmd(p, x, cfg)


def _apply_moe_gspmd(p, x, cfg: ModelConfig):
    """GSPMD scatter/gather dispatch (fallback path)."""
    m = cfg.moe
    b, s, d = x.shape
    tokens = b * s
    xt = x.reshape(tokens, d)
    logits = (xt @ p["router"]).astype(jnp.float32)        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    top_p, top_e = jax.lax.top_k(probs, m.top_k)           # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    cap = _capacity(tokens, m)
    # position of each (token, k) assignment within its expert's capacity
    # buffer via ARGSORT over expert ids (O(A log A), A = T*k) — the
    # one-hot cumsum alternative materializes an (A, E) tensor that at
    # DeepSeek-V2 scale is a replicated ~1 GiB s32 monster plus a 1 GB
    # all-gather per layer (§Perf iteration 3, measured)
    A = tokens * m.top_k
    flat_e = top_e.reshape(A)
    order = jnp.argsort(flat_e, stable=True)                       # (A,)
    counts = jnp.zeros((m.n_experts,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                           # (E,)
    ranks_sorted = jnp.arange(A, dtype=jnp.int32) - starts[flat_e[order]]
    pos = jnp.zeros((A,), jnp.int32).at[order].set(ranks_sorted)
    pos = pos.reshape(tokens, m.top_k)
    keep = pos < cap                                               # (T,k)

    # scatter each kept assignment into the (E, cap, d) expert buffer with
    # 2D indices; the buffer itself is pinned to the EP axis so GSPMD
    # emits dispatch communication instead of a replicated-buffer AR
    from repro.sharding.hints import hint, hint_tokens
    e_idx = jnp.where(keep, top_e, m.n_experts).reshape(A)   # OOB = drop
    p_idx = jnp.minimum(pos, cap - 1).reshape(A)
    expert_in = hint(jnp.zeros((m.n_experts, cap, d), x.dtype),
                     "model", None, None)
    expert_in = expert_in.at[e_idx, p_idx].add(
        jnp.repeat(xt, m.top_k, axis=0), mode="drop")
    expert_in = hint(expert_in, "model", None, None)
    expert_out = jax.vmap(lambda w, h: apply_mlp(w, h, cfg.mlp))(
        p["experts"], expert_in)
    expert_out = hint(expert_out, "model", None, None)

    slot = top_e * cap + p_idx.reshape(tokens, m.top_k)
    gathered = expert_out.reshape(m.n_experts * cap, d)[
        jnp.minimum(slot, m.n_experts * cap - 1).reshape(-1)]      # (A,d)
    gathered = hint_tokens(gathered.reshape(tokens, m.top_k, d))
    w = (top_p * keep).astype(x.dtype)                             # (T,k)
    y = jnp.einsum("tkd,tk->td", gathered, w)

    if m.n_shared:
        sh = jax.vmap(lambda w: apply_mlp(w, xt, cfg.mlp))(p["shared"])
        y = y + jnp.sum(sh, axis=0)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, m.n_experts), axis=1), axis=0)
    aux = m.router_aux_coef * m.n_experts * jnp.sum(me * ce)
    return y.reshape(b, s, d).astype(x.dtype), aux
