"""Neural-net building blocks (pure functional JAX).

Every block is a pair ``init_*(key, cfg, ...) -> params`` /
``apply(params, x, ...) -> y`` over plain dict pytrees, so layer stacks can
be created with ``jax.vmap`` over per-layer keys and executed with
``jax.lax.scan`` (compact HLO — essential for 512-way GSPMD partitioning
of 80-95 layer models).

Attention (:func:`sdpa`) takes the Pallas flash kernel where
:mod:`repro.kernels.policy` allows it and the pure-XLA math otherwise.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .config import ModelConfig


def _init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d, dtype):
    return {"w": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps=1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * p["w"]


def init_layernorm(d, dtype):
    return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layer_norm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y.astype(x.dtype)) * p["w"] + p["b"]


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
                sections=(16, 24, 24)) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE: positions3 (3, B, S) = (t, h, w) ids;
    the head-dim frequency bands are partitioned into 3 sections, each
    rotated by its own position stream [arXiv:2409.12191]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    # section id per frequency band
    sec = jnp.concatenate([jnp.full((s,), i) for i, s in enumerate(sections)])
    sec = sec[: hd // 2]
    # gather per-band positions: band b uses the positions3[sec[b]] stream
    p = positions3.astype(jnp.float32)                  # (3,B,S)
    ang = p[sec, :, :]                                  # (hd/2,B,S)
    ang = jnp.moveaxis(ang, 0, -1) * freqs              # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def init_mlp(key, d, ff, kind, dtype, bias=False):
    ks = jax.random.split(key, 3)
    p = {"up": _init(ks[1], (d, ff), dtype),
         "down": _init(ks[2], (ff, d), dtype)}
    if kind in ("swiglu", "geglu"):
        p["gate"] = _init(ks[0], (d, ff), dtype)
    return p


def apply_mlp(p, x, kind):
    up = x @ p["up"]
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["gate"]) * up
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["gate"]) * up
    else:
        h = jax.nn.gelu(up)
    return h @ p["down"]


# ---------------------------------------------------------------------------
# attention (GQA, optional sliding window, cross-attention, KV cache)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype, cross=False):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {"wq": _init(ks[0], (d, H * hd), dtype),
         "wk": _init(ks[1], (d, K * hd), dtype),
         "wv": _init(ks[2], (d, K * hd), dtype),
         "wo": _init(ks[3], (H * hd, d), dtype)}
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd)


def cache_write(buf, new, idx):
    """Write ``new`` (B, s, ...) into ``buf`` (B, S, ...) at position ``idx``.

    Single-token decode uses a masked `where(iota == idx)` update instead
    of dynamic_update_slice: with the cache SEQUENCE-sharded over the TP
    axis, DUS at a dynamic index triggers GSPMD's "involuntary full
    rematerialization" (an all-gather of the whole cache per layer per
    token — §Perf iteration 1); the masked form is elementwise and stays
    entirely shard-local (XLA fuses it into a masked copy).
    """
    if new.shape[1] == 1:
        ids = jnp.arange(buf.shape[1])
        mask = (ids == idx).reshape((1, -1) + (1,) * (buf.ndim - 2))
        return jnp.where(mask, new.astype(buf.dtype), buf)
    start = (0, idx) + (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), start)


_CHUNK_Q = 1024
_CHUNK_THRESHOLD = 8 * 1024 * 1024  # sq*sk above which q-chunking kicks in


def _sdpa_block(q, k, v, *, causal, window, q_offset, length_mask,
                kv_seq_hint: bool = False):
    """GQA attention WITHOUT materializing repeated K/V: queries are
    grouped as (b, sq, kv_heads, rep, hd) and contracted against the
    un-repeated cache.  (`jnp.repeat` over heads lowers to a
    broadcast_in_dim that GSPMD implements by ALL-GATHERING a
    sequence-sharded KV cache — 2.1 GB/layer at decode_32k;
    §Perf iteration 1b.)

    ``kv_seq_hint`` pins the score tensor's key dim to the ``model`` axis
    (decode path: the cache is sequence-sharded, so scores stay sharded
    and only softmax stats + the (b,h,1,hd) output cross the axis)."""
    b, sq, h, hd = q.shape
    _, sk, kh, _ = k.shape
    rep = h // kh
    if kv_seq_hint:
        # decode path: grouped heads, un-repeated K/V (repeat would
        # all-gather the sequence-sharded cache)
        qg = q.reshape(b, sq, kh, rep, hd)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
    else:
        # train/prefill: K/V are fresh activations (repeat is local);
        # the grouped reshape would mis-align head sharding when H does
        # not divide the TP degree (phi3's 40 heads on TP16 regressed
        # memory 2x — measured, reverted for this path)
        kq = jnp.repeat(k, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq).astype(jnp.float32)
    logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    if kv_seq_hint:
        from repro.sharding.hints import batch_axes, hint
        logits = hint(logits, batch_axes(), None, None, None, "model")
    qi = jnp.arange(sq) + q_offset
    ki = jnp.arange(sk)
    if causal or window is not None:
        mask = jnp.ones((sq, sk), bool)
        if causal:
            mask &= ki[None, :] <= qi[:, None]
        if window is not None:
            mask &= ki[None, :] > qi[:, None] - window
        mshape = (1,) * (logits.ndim - 2) + (sq, sk)
        logits = jnp.where(mask.reshape(mshape), logits, -1e30)
    if length_mask is not None:  # (B, Sk) valid-key mask
        lshape = (b,) + (1,) * (logits.ndim - 3) + (1, sk)
        logits = jnp.where(length_mask.reshape(lshape), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if kv_seq_hint:
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
        return out.reshape(b, sq, h, v.shape[-1])
    vq = jnp.repeat(v, rep, axis=2)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vq)


def sdpa(q, k, v, *, causal: bool, window: int | None = None,
         q_offset: int = 0, length_mask: jnp.ndarray | None = None,
         kv_seq_hint: bool = False):
    """Reference scaled-dot-product attention with GQA broadcast.

    q: (B,Sq,H,hd); k/v: (B,Sk,K,hd).  On TPU the Pallas flash kernel
    (kernels/flash_attention.py) replaces this math; shapes and semantics
    are identical (see kernels/ref.py).

    Long sequences take a query-chunked path (scan over Sq blocks,
    materializing only (chunk, Sk) score tiles) so the XLA fallback stays
    O(S) in memory — required to even lower prefill_32k, where the naive
    (B,H,S,S) fp32 score tensor would be tens of GiB per device.
    """
    b, sq, h, hd = q.shape
    _, sk, kh, _ = k.shape
    from repro.kernels.policy import use_pallas
    if (use_pallas() and length_mask is None and q_offset == 0
            and sq % 128 == 0 and sk % 128 == 0 and hd % 8 == 0):
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                              v.swapaxes(1, 2), causal=causal, window=window,
                              interpret=jax.default_backend() != "tpu")
        return out.swapaxes(1, 2)
    if sq * sk > _CHUNK_THRESHOLD and sq % _CHUNK_Q == 0 and sq > _CHUNK_Q:
        nc = sq // _CHUNK_Q
        qc = q.reshape(b, nc, _CHUNK_Q, h, hd).swapaxes(0, 1)

        def body(carry, inp):
            qi, idx = inp
            out = _sdpa_block(qi, k, v, causal=causal, window=window,
                              q_offset=q_offset + idx * _CHUNK_Q,
                              length_mask=length_mask,
                              kv_seq_hint=kv_seq_hint)
            return carry, out

        _, outs = jax.lax.scan(body, 0, (qc, jnp.arange(nc)))
        # output head dim follows v (MLA uses v_head_dim != qk head dim)
        return outs.swapaxes(0, 1).reshape(b, sq, h, v.shape[-1])
    return _sdpa_block(q, k, v, causal=causal, window=window,
                       q_offset=q_offset, length_mask=length_mask,
                       kv_seq_hint=kv_seq_hint)


def apply_attention(p, x, cfg: ModelConfig, *, positions=None,
                    positions3=None, causal=True, window=None,
                    cache=None, kv_src=None, use_rope=True):
    """Self- or cross-attention.  ``cache`` (decode): dict with
    k/v (B, S_max, K, hd) and index; returns (y, new_cache)."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"] + (p.get("bq", 0.0) if "bq" in p else 0.0)
    src = x if kv_src is None else kv_src
    k = src @ p["wk"] + (p.get("bk", 0.0) if "bk" in p else 0.0)
    v = src @ p["wv"] + (p.get("bv", 0.0) if "bv" in p else 0.0)
    q = _split_heads(q, H, hd)
    k = _split_heads(k, K, hd)
    v = _split_heads(v, K, hd)
    if use_rope and kv_src is None:
        if cfg.mrope and positions3 is not None:
            q = apply_mrope(q, positions3, cfg.rope_theta)
            k = apply_mrope(k, positions3, cfg.rope_theta)
        elif positions is not None:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        idx = cache["idx"]                                 # scalar int32
        b = x.shape[0]
        cache_len = cache["k"].shape[1]
        if window is not None and cache_len <= window:
            # ring buffer: the cache IS the sliding window; every live slot
            # is in-window by construction (keys carry their write-time RoPE)
            slot = idx % cache_len
            ck = cache_write(cache["k"], k, slot)
            cv = cache_write(cache["v"], v, slot)
            valid = jnp.arange(cache_len) < (idx + x.shape[1])
            y = sdpa(q, ck, cv, causal=False, kv_seq_hint=True,
                     length_mask=jnp.broadcast_to(valid[None, :],
                                                  (b, cache_len)))
        else:
            ck = cache_write(cache["k"], k, idx)
            cv = cache_write(cache["v"], v, idx)
            valid = jnp.arange(ck.shape[1]) < (idx + x.shape[1])
            y = sdpa(q, ck, cv, causal=False, window=window, q_offset=idx,
                     kv_seq_hint=True,
                     length_mask=jnp.broadcast_to(valid[None, :],
                                                  (b, ck.shape[1])))
        new_cache = {"k": ck, "v": cv, "idx": idx + x.shape[1]}
    else:
        y = sdpa(q, k, v, causal=causal, window=window)
    b, s = x.shape[:2]
    out = y.reshape(b, s, H * hd) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2 [arXiv:2405.04434])
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 7)
    p = {}
    if m.q_lora:
        p["wq_a"] = _init(ks[0], (d, m.q_lora), dtype)
        p["wq_b"] = _init(ks[1], (m.q_lora, H * qd), dtype)
    else:
        p["wq"] = _init(ks[0], (d, H * qd), dtype)
    # joint KV low-rank compression + decoupled rope key
    p["wkv_a"] = _init(ks[2], (d, m.kv_lora + m.qk_rope_dim), dtype)
    p["wkv_b"] = _init(ks[3], (m.kv_lora, H * (m.qk_nope_dim + m.v_head_dim)),
                       dtype)
    p["wo"] = _init(ks[4], (H * m.v_head_dim, d), dtype)
    return p


def apply_mla(p, x, cfg: ModelConfig, *, positions=None, causal=True,
              cache=None):
    """MLA attention.  Decode cache stores only the compressed latent
    (kv_lora + rope dims per token) — the paper's KV-cache saving."""
    m = cfg.mla
    H = cfg.n_heads
    b, s, _ = x.shape
    qd = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora:
        q = (x @ p["wq_a"]) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(b, s, H, qd)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    if positions is not None:
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    latent = x @ p["wkv_a"]                                # (b,s,lora+rope)
    c_kv, k_rope = jnp.split(latent, [m.kv_lora], axis=-1)
    k_rope = k_rope[:, :, None, :]                         # (b,s,1,rope)
    if positions is not None:
        k_rope = apply_rope(k_rope, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        idx = cache["idx"]
        c_all = cache_write(cache["c_kv"], c_kv, idx)
        r_all = cache_write(cache["k_rope"], k_rope[:, :, 0, :], idx)
        new_cache = {"c_kv": c_all, "k_rope": r_all, "idx": idx + s}
        kv_len = c_all.shape[1]
        valid = jnp.arange(kv_len) < (idx + s)
        c_kv_full, k_rope_full = c_all, r_all[:, :, None, :]
        q_offset = idx
    else:
        c_kv_full, k_rope_full = c_kv, k_rope
        valid = None
        q_offset = 0

    kv = (c_kv_full @ p["wkv_b"]).reshape(
        b, c_kv_full.shape[1], H, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_full,
                                  (*k_nope.shape[:3], m.qk_rope_dim))], -1)
    qh = jnp.concatenate([q_nope, q_rope], axis=-1)
    y = sdpa(qh, k, v, causal=causal and cache is None,
             q_offset=q_offset, kv_seq_hint=cache is not None,
             length_mask=None if valid is None
             else jnp.broadcast_to(valid[None, :], (b, valid.shape[0])))
    out = y.reshape(b, s, H * m.v_head_dim) @ p["wo"]
    return out, new_cache
