"""The plain float32 twin of the benchmark's training step, apart from
the model: the seed's weights and AdamW as the program's optimizer
applies it.

The model's own part, its parameters and its loss, comes from the
configuration's architecture module (``bench/archs/<arch>.py``), which
imports nothing of the program under test either.  Parameters are a
flat dict named as the program names them.  Weights are made from the
seed on the device in one jitted call (:func:`weight_maker`): norm
scales at one, everything else ``N(0, 0.05^2)``, each leaf from its own
fold of the seed's key, in the order of the architecture's
``param_shapes``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of any size up to 64 bits as two 32-bit words."""
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def weight_maker(arch, m: dict):
    """``make(lo, hi) -> {name: float32 array}`` jitted: every weight of
    model ``m`` (``arch.param_shapes(m)``) from the seed words, on the
    default device, in one call."""
    shapes = arch.param_shapes(m)

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


def loss_and_grad(arch, m: dict):
    """Jitted ``(params, ids, labels) -> (loss, grads)`` of
    ``arch.loss``; the caller fixes the matmul precision around the
    call."""
    return jax.jit(jax.value_and_grad(
        lambda p, ids, labels: arch.loss(p, ids, labels, m)))


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
