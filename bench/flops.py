"""Operations and bytes of the benchmark's kernels, from shapes alone.

A model's training work per token depends on its block, and each
architecture module (``bench/archs/<arch>.py``) counts its own; the
kernels that several architectures share are counted here.
Recomputed work does not count.
"""

from __future__ import annotations


def flash_attention(batch: int, heads: int, kv_heads: int, seq: int,
                    head_dim: int, itemsize: int = 4,
                    window: int | None = None) -> tuple[float, float]:
    """Operations and HBM bytes of one causal flash-attention forward
    call: ``QK^T`` and ``PV`` over the visible query-key pairs, and q,
    k, v read and the output written once.  A query sees the keys up
    to its own position, ``seq * (seq + 1) / 2`` pairs in all, or with
    a ``window`` at most the ``window`` latest of them, itself
    included."""
    if window is None or window >= seq:
        pairs = seq * (seq + 1) / 2
    else:
        pairs = window * (window + 1) / 2 + (seq - window) * window
    flops = 4.0 * batch * heads * pairs * head_dim
    elems = 2 * batch * heads * seq * head_dim \
        + 2 * batch * kv_heads * seq * head_dim
    return flops, float(elems * itemsize)
