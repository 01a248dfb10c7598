"""The plain float32 twin of the benchmark's training step.

Written from the published equations of a pre-norm decoder, in plain
``jax.numpy``, and importing nothing of the program under test: the
loss of the graph-IR block stack (``models.graph_block.build_block``:
no RoPE, loss ``mean(softmax(logits)[labels])``) and AdamW as the
program's host optimizer applies it.  A CPU test pins this copy to the
program's own ``graph_block.reference_loss``, so a later change to the
program cannot move the yardstick.

Parameters are a flat dict named as the program names them
(``embed``, ``l<i>/wq`` ... ``final_norm``, ``lm_head``).  Weights are
made from the seed on the device in one jitted call
(:func:`weight_maker`): norm scales at one, everything else
``N(0, 0.05^2)``, each leaf from its own fold of the seed's key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def param_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter of the block stack of model ``m`` (a config dict
    of ``bench/configs``), in the program's order and names."""
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


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of any size up to 64 bits as two 32-bit words."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def weight_maker(m: dict):
    """``make(lo, hi) -> {name: float32 array}`` jitted: every weight of
    model ``m`` from the seed words, on the default device, in one
    call."""
    shapes = param_shapes(m)

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            if name.split("/")[-1].endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = 0.05 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)


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


def loss_and_grad(m: dict):
    """Jitted ``(params, ids, labels) -> (loss, grads)``; the caller
    fixes the matmul precision around the call."""
    return jax.jit(jax.value_and_grad(
        lambda p, ids, labels: loss(p, ids, labels, m)))


def adamw(opt: dict):
    """AdamW with global-norm clipping, linear warm-up, bias correction
    and decoupled weight decay, as two jitted parts: ``clip(grads) ->
    scale`` over all leaves, then per leaf ``update(p, g, m, v, scale,
    count) -> (p, m, v, g * scale)`` with ``count`` the number of the
    step being applied (1 first)."""
    b1, b2 = opt["b1"], opt["b2"]

    def clip(grads):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        return jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))

    def update(p, g, mom, vel, scale, count):
        g = g * scale
        mom = b1 * mom + (1 - b1) * g
        vel = b2 * vel + (1 - b2) * g * g
        c = count.astype(jnp.float32)
        bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
        lr = opt["lr"] * jnp.minimum(c / max(opt["warmup_steps"], 1), 1.0)
        step = (mom / bc1) / (jnp.sqrt(vel / bc2) + opt["eps"]) \
            + opt["weight_decay"] * p
        return p - lr * step, mom, vel, g

    return jax.jit(clip), jax.jit(update)
