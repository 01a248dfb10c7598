"""Flash attention Pallas TPU kernel (GQA-aware, causal / sliding-window).

TPU-native design (not a CUDA port):
  * Grid ``(batch, q_heads, num_q_blocks, num_k_blocks)`` with the k-block
    dimension marked ``arbitrary`` (sequential) so the online-softmax
    accumulators live in VMEM scratch across k iterations.
  * BlockSpecs tile Q/K/V into (block_q, head_dim) / (block_k, head_dim)
    VMEM windows; head_dim and block sizes are MXU-aligned (128 multiples).
  * GQA is expressed in the K/V index maps (q-head h reads kv-head
    ``h // (H // K)``) — no materialized ``jnp.repeat`` over heads, which
    would multiply HBM traffic by H/K.
  * Causal + window masks are applied with 2D iota inside the kernel;
    fully-masked k blocks are skipped by the index-map-level early loop
    bound (conservative: we rely on @pl.when zero-cost masking here).

Numerics follow the standard streaming softmax: running row max ``m``,
normalizer ``l`` and accumulator ``acc`` in fp32 scratch.  The two
matmuls follow the caller's ``jax.default_matmul_precision`` like the
XLA matmuls around the kernel (:func:`contract_precision`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def contract_precision(dtype):
    """Precision of the kernel's matmuls, read when it is traced (the
    setting is part of the jit key).  Mosaic contracts at one bfloat16
    pass (``DEFAULT``) or at fp32 (``HIGHEST``): float32 inputs take
    fp32 when ``jax.default_matmul_precision`` asks for more than one
    pass, and one pass otherwise, as XLA's float32 matmuls do on a TPU.
    Other dtypes always take one pass.  A ``DotAlgorithmPreset`` name
    asks for one pass when its operands are 16-bit or narrower and it
    names no pass count (``BF16_BF16_F32``), for more otherwise
    (``F32_F32_F32``, ``BF16_BF16_F32_X6``).  The result is always
    explicit, since a preset left to ``lax.dot_general``'s default
    does not lower in Mosaic."""
    one = jax.lax.Precision.DEFAULT
    if jnp.dtype(dtype) != jnp.float32:
        return one
    setting = jax.config.jax_default_matmul_precision
    try:
        one_pass = jax.lax.Precision(setting) == one
    except ValueError:          # a DotAlgorithmPreset name
        one_pass = ("_X" not in setting
                    and not setting.startswith(("F32", "TF32", "F64")))
    return one if one_pass else jax.lax.Precision.HIGHEST


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int | None, block_q: int, block_k: int,
            num_kb: int, sm_scale: float, precision):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                  # (bq, bk)

    q_ids = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_ids = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_ids <= q_ids
    if window is not None:
        mask &= k_ids > q_ids - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # (bq, LANES)
    m_cur = jnp.max(s, axis=1, keepdims=True)         # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)                   # (bq, LANES)
    p = jnp.exp(s - m_new[:, :1])                     # (bq, bk)
    l_new = l_scr[...] * alpha \
        + jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True),
                           m_prev.shape)
    acc = acc_scr[...] * alpha[:, :1] \
        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              precision=precision,
                              preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(kb == num_kb - 1)
    def _finish():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, K, Sk, D), H % K == 0. Returns (B,H,Sq,D).

    On CPU pass ``interpret=True`` (the validation mode); on TPU the same
    call compiles to a fused VMEM-tiled kernel.
    """
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    assert h % kh == 0, (h, kh)
    rep = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    num_qb, num_kb = sq // block_q, sk // block_k
    sm_scale = 1.0 / (d ** 0.5)

    grid = (b, h, num_qb, num_kb)
    kern = functools.partial(
        _kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, num_kb=num_kb, sm_scale=sm_scale,
        precision=contract_precision(q.dtype))

    call = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, rep=rep: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki, rep=rep: (bi, hi // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),   # running max m
            pltpu.VMEM((block_q, LANES), jnp.float32),   # normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )
    # Mosaic cannot lower 64-bit scalars (Python ints and floats turn
    # into them under x64), so the kernel is always traced with 32-bit
    # defaults, also inside a caller's x64 scope (the exact float64
    # reduction of a sharded train step is traced in one)
    with jax.enable_x64(False):
        return call(q, k, v)
