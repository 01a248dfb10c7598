"""Operations and bytes of the benchmark's work, from shapes alone.

Training counts the operations the forward and backward passes need:
``6 x`` the matmul parameters each token meets (the layers' projections
and the LM head; the embedding gather counts 0), plus causal attention,
``6 * S * H * hd`` per layer and token (``QK^T`` and ``PV`` over the
``~S/2`` keys a query sees, forward ``2 S H hd``, backward twice that).
Recomputed work does not count.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Matmul weights one token passes through in model ``m`` (a
    ``bench/configs`` dict): every layer's projections and the head."""
    d, h, k, hd, ff = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * ff
    return m["num_hidden_layers"] * layer + d * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward operations per token of a ``seq``-long
    causal sequence."""
    attn = 6 * seq * m["num_attention_heads"] * m["head_dim"]
    return 6.0 * matmul_params(m) + m["num_hidden_layers"] * attn


def flash_attention(batch: int, heads: int, kv_heads: int, seq: int,
                    head_dim: int, itemsize: int = 4
                    ) -> tuple[float, float]:
    """Operations and HBM bytes of one causal flash-attention forward
    call: ``QK^T`` and ``PV`` over the ``seq * (seq + 1) / 2`` visible
    query-key pairs, and q, k, v read and the output written once."""
    pairs = seq * (seq + 1) / 2
    flops = 4.0 * batch * heads * pairs * head_dim
    elems = 2 * batch * heads * seq * head_dim \
        + 2 * batch * kv_heads * seq * head_dim
    return flops, float(elems * itemsize)
