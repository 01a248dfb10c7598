"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU-native layout decisions (vs the paper's CUDA kernel):
  * Grid ``(batch, heads, num_chunks)`` — chunks are ``arbitrary``
    (sequential) so the inter-chunk SSM state (head_dim x d_state, fp32)
    persists in VMEM scratch; batch/head dims are parallel.
  * Per-chunk work is three MXU matmuls: the intra-chunk quadratic
    (C_c B_c^T ⊙ L) x̄, the state read-out C_c S^T, and the state update
    x̄^T (B_c ⊙ decay) — all with chunk and d_state padded to 128 lanes.
  * The decay factors are computed from ``la = dt * A`` which the wrapper
    precomputes elementwise (keeps A out of SMEM scalar plumbing).

The oracle is :func:`repro.kernels.ref.ssd_scan_ref` (the model's own
pure-jnp chunked scan, itself validated against step-by-step decode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(la_ref, xbar_ref, b_ref, c_ref, y_ref, state_out_ref, state_scr,
            *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    la = la_ref[0, :, 0].astype(jnp.float32).reshape(chunk, 1)   # (q,1)
    xbar = xbar_ref[0, :, 0].astype(jnp.float32)                 # (q,p)
    B = b_ref[0].astype(jnp.float32)                             # (q,n)
    C = c_ref[0].astype(jnp.float32)                             # (q,n)

    cum = jnp.cumsum(la, axis=0)                                 # (q,1)
    total = cum[chunk - 1, 0]

    # intra-chunk: (C_i . B_j) * exp(cum_i - cum_j) for i >= j
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    diff = cum - cum.reshape(1, chunk)                           # (q,q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(qi >= kj, jnp.exp(diff), 0.0)
    y_intra = jax.lax.dot_general(scores * L, xbar,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # carried-state contribution: exp(cum_i) * (C_i . S)
    state = state_scr[...]                                       # (p,n)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0, :, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S' = S * exp(total) + x̄^T (B ⊙ exp(total - cum))
    decay_to_end = jnp.exp(total - cum)                          # (q,1)
    state_new = state * jnp.exp(total) + jax.lax.dot_general(
        xbar, B * decay_to_end, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = state_new

    @pl.when(ci == num_chunks - 1)
    def _finish():
        state_out_ref[0, 0] = state_new.astype(state_out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD scan.  Same contract as the oracle:
    x: (b,s,h,p); dt: (b,s,h) (softplus-ed); A: (h,); B/C: (b,s,n).
    Returns (y (b,s,h,p), final_state (b,h,p,n))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    la = (dt * A[None, None, :]).astype(jnp.float32)      # (b,s,h)
    xbar = x * dt[..., None].astype(x.dtype)

    grid = (b, h, nc)
    kern = functools.partial(_kernel, chunk=chunk, num_chunks=nc)
    y, state = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, chunk, 1, p),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(la, xbar, B, C)
    return y, state
