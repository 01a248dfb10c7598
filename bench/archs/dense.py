"""The dense pre-norm decoder: grouped-query causal attention (with an
optional QKV bias), a SwiGLU MLP, RMSNorm, and a tied or untied head.

An architecture module of the benchmark, found by the ``"arch"`` key
of a configuration in ``bench/configs``.  It gives what depends on the
block: the program as the system builds it, the parameters in the
program's order, the plain float32 loss, the training work per token,
the flash-attention calls of a step, and the CPU-size twin.

The program is ``models.graph_block``'s block stack (no RoPE; loss
``mean(softmax(logits)[labels])``).  The loss is written here from the
published equations in plain ``jax.numpy`` and imports nothing of the
program under test; a CPU test pins it to the program's own twin
(``graph_block.reference_loss``) and to the program's train step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops

#: widths of the CPU-size twin
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            head_dim=16, vocab_size=256, num_hidden_layers=2)


def model_config(m: dict):
    """The program's ``ModelConfig`` for configuration ``m`` (a
    ``bench/configs`` dict)."""
    from repro.models.config import ModelConfig

    if m["hidden_act"] != "silu":
        raise ValueError(f"{m['name']}: only SwiGLU blocks are built")
    return ModelConfig(
        name=m["name"], family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m["head_dim"],
        qkv_bias=m["qkv_bias"], mlp="swiglu", norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"], source=m["source"])


def program(m: dict, batch: int, seq: int, layout: dict):
    """The block stack of model ``m`` for a ``batch x seq`` step under
    ``layout`` (``dp``, ``tp``), as an ``api.Program``."""
    from repro.models.graph_block import block_program

    return block_program(model_config(m), batch=batch, seq=seq,
                         n_layers=m["num_hidden_layers"],
                         dp=layout["dp"], tp=layout["tp"], pp=1)


def param_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter of the block stack, in the program's order and
    names (``embed``, ``l<i>/wq`` ... ``final_norm``, ``lm_head``)."""
    d, h, k, hd = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    shapes: dict[str, tuple[int, ...]] = {"embed": (m["vocab_size"], d)}
    for i in range(m["num_hidden_layers"]):
        p = f"l{i}/"
        shapes[p + "attn_norm"] = (d,)
        shapes[p + "wq"] = (d, h * hd)
        shapes[p + "wk"] = (d, k * hd)
        shapes[p + "wv"] = (d, k * hd)
        if m["qkv_bias"]:
            shapes[p + "bq"] = (h * hd,)
            shapes[p + "bk"] = (k * hd,)
            shapes[p + "bv"] = (k * hd,)
        shapes[p + "wo"] = (h * hd, d)
        shapes[p + "mlp_norm"] = (d,)
        shapes[p + "w_up"] = (d, m["intermediate_size"])
        shapes[p + "w_gate"] = (d, m["intermediate_size"])
        shapes[p + "w_down"] = (m["intermediate_size"], d)
    shapes["final_norm"] = (d,)
    if not m["tie_word_embeddings"]:
        shapes["lm_head"] = (d, m["vocab_size"])
    return shapes


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def attention(x, p, m: dict):
    """Causal grouped-query attention of one layer (no RoPE)."""
    b, s, _ = x.shape
    h, k, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    q = x @ p["wq"]
    kk = x @ p["wk"]
    v = x @ p["wv"]
    if m["qkv_bias"]:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    kk = jnp.repeat(kk.reshape(b, s, k, hd), h // k, axis=2)
    v = jnp.repeat(v.reshape(b, s, k, hd), h // k, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, s, h * hd) @ p["wo"]


def loss(params: dict, ids, labels, m: dict):
    """Mean probability the model gives each label: the graph IR's
    proxy loss over the pre-norm SwiGLU block stack."""
    eps = m["rms_norm_eps"]
    x = params["embed"][ids]
    for i in range(m["num_hidden_layers"]):
        p = {n.split("/", 1)[1]: w for n, w in params.items()
             if n.startswith(f"l{i}/")}
        x = x + attention(rms_norm(x, p["attn_norm"], eps), p, m)
        h = rms_norm(x, p["mlp_norm"], eps)
        x = x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) \
            @ p["w_down"]
    x = rms_norm(x, params["final_norm"], eps)
    head = params["embed"].T if m["tie_word_embeddings"] \
        else params["lm_head"]
    probs = jax.nn.softmax(x @ head, axis=-1)
    return jnp.take_along_axis(probs, labels[..., None], -1)[..., 0].mean()


def matmul_params(m: dict) -> int:
    """Matmul weights one token passes through: every layer's
    projections and the head (the embedding gather counts 0)."""
    d, h, k, hd, ff = (m["hidden_size"], m["num_attention_heads"],
                       m["num_key_value_heads"], m["head_dim"],
                       m["intermediate_size"])
    layer = d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * ff
    return m["num_hidden_layers"] * layer + d * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward operations per token of a ``seq``-long
    causal sequence: ``6 x`` the matmul parameters, plus causal
    attention, ``6 * S * H * hd`` per layer (``QK^T`` and ``PV`` over
    the ``~S/2`` keys a query sees, forward ``2 S H hd``, backward twice
    that)."""
    attn = 6 * seq * m["num_attention_heads"] * m["head_dim"]
    return 6.0 * matmul_params(m) + m["num_hidden_layers"] * attn


def attention_calls(m: dict, traffic: dict, layout: dict
                    ) -> list[tuple[float, float]]:
    """``(ops, bytes)`` of each flash-attention forward call of one
    step on one chip, in layer order: every layer's is full causal
    attention over the chip's share of the batch and heads."""
    call = flops.flash_attention(
        traffic["batch"] // layout["dp"],
        m["num_attention_heads"] // layout["tp"],
        m["num_key_value_heads"] // layout["tp"], traffic["seq"],
        m["head_dim"])
    return [call] * m["num_hidden_layers"]


def tiny(m: dict) -> dict:
    """``m`` at CPU size: every width this module reads shrunk, grouped
    queries kept grouped."""
    kv = 2 if m["num_key_value_heads"] < m["num_attention_heads"] else 4
    return dict(m, num_key_value_heads=kv, **TINY)
