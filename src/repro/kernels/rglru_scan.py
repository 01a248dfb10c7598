"""RG-LRU linear-recurrence Pallas TPU kernel.

The gated linear recurrence ``h_t = a_t h_{t-1} + b_t`` is memory-bound;
the TPU-native layout is:
  * Grid ``(batch, width_blocks, num_chunks)`` — chunks sequential
    (``arbitrary``) carrying the hidden state in a (1, block_w) fp32 VMEM
    scratch; batch and width are embarrassingly parallel (the recurrence
    couples only the time dimension).
  * Within a chunk the recurrence is unrolled with ``fori_loop`` over
    rows of the (chunk, block_w) VMEM tile — sublane-major traversal, so
    each step is a fused multiply-add over one 8x128-aligned row.
  * a_t and b_t are precomputed elementwise by the wrapper
    (``a = exp(-c softplus(lam) r)``, ``b = sqrt(1-a^2) (i * x)``), keeping
    the kernel a pure scan.

Oracle: :func:`repro.kernels.ref.rglru_ref` (associative-scan formulation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_C = 8.0


def _kernel(a_ref, b_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)          # (chunk, w)
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h = carry                              # (1, w)
        h = a[t][None, :] * h + b[t][None, :]
        y_ref[0, t, :] = h[0].astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_pallas(x, r, i, lam, *, chunk: int = 128, block_w: int = 128,
                 interpret: bool = False):
    """RG-LRU scan.  x, r, i: (b, s, w); lam: (w,).  Returns h: (b, s, w)."""
    b, s, w = x.shape
    assert s % chunk == 0, (s, chunk)
    block_w = min(block_w, w)
    assert w % block_w == 0, (w, block_w)
    nc = s // chunk

    log_a = -_C * jax.nn.softplus(lam)[None, None, :] * r.astype(jnp.float32)
    a = jnp.exp(log_a)
    bterm = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * (i * x).astype(jnp.float32)

    grid = (b, w // block_w, nc)
    kern = functools.partial(_kernel, chunk=chunk)
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_w),
                         lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, chunk, block_w),
                         lambda bi, wi, ci: (bi, ci, wi)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_w),
                               lambda bi, wi, ci: (bi, ci, wi)),
        out_shape=jax.ShapeDtypeStruct((b, s, w), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, bterm)
    return y
