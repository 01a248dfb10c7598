"""Per-architecture smoke tests (reduced variants of each assigned family).

Each test instantiates the REDUCED config (<=2 layers / pattern,
d_model<=256, <=4 experts), runs one forward + one train step on CPU, and
asserts output shapes + finiteness.  Decode paths are validated against
the full forward (teacher-forcing equivalence).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models.model import (_run_encoder, decode_step, forward,
                                init_decode_state, init_params, loss_fn)

ASSIGNED = [a for a in ARCHS if not a.startswith("llama")]


def _batch(cfg, key, B=2, S=16):
    batch = {"labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.input_kind == "embeds":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model)) * 0.02
        batch["positions3"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)).astype(jnp.int32)
    elif cfg.input_kind == "audio":
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
        batch["audio_embeds"] = jax.random.normal(
            key, (B, cfg.encdec.n_frames, cfg.d_model)) * 0.02
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    B, S = 2, 16
    batch = _batch(cfg, key, B, S)

    logits, aux = jax.jit(lambda p, b: forward(p, b, cfg))(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))

    # one SGD step must produce finite loss + grads and change the params
    def step(p, b):
        (loss, m), grads = jax.value_and_grad(
            lambda q: loss_fn(q, b, cfg), has_aux=True)(p)
        new_p = jax.tree.map(lambda w, g: w - 1e-3 * g, p, grads)
        return loss, new_p

    loss, new_params = jax.jit(step)(params, batch)
    assert bool(jnp.isfinite(loss))
    leaves_before = jax.tree.leaves(params)
    leaves_after = jax.tree.leaves(new_params)
    assert any(not np.allclose(a, b) for a, b in
               zip(leaves_before, leaves_after))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    B, S = 2, 12
    batch = _batch(cfg, key, B, S)
    logits_full, _ = forward(params, batch, cfg)

    enc_out = _run_encoder(params, batch, cfg) if cfg.encdec else None
    state = init_decode_state(cfg, B, max_len=S, enc_out=enc_out)
    step = jax.jit(lambda p, s, b: decode_step(p, s, b, cfg))
    outs = []
    for t in range(S):
        sb = {}
        if cfg.input_kind == "embeds":
            sb["embeds"] = batch["embeds"][:, t:t + 1]
            sb["positions3"] = batch["positions3"][:, :, t:t + 1]
        else:
            sb["tokens"] = batch["tokens"][:, t:t + 1]
        lg, state = step(params, state, sb)
        outs.append(lg[:, 0])
    logits_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full),
                               atol=2e-3, rtol=1e-3)


def test_remat_forward_matches():
    cfg = get_config("qwen2-1.5b").reduced()
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    batch = _batch(cfg, key)
    l1, _ = forward(params, batch, cfg, remat=False)
    l2, _ = forward(params, batch, cfg, remat=True)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)


def test_sliding_window_attention_masks_far_tokens():
    """Hybrid local attention must ignore tokens beyond the window."""
    cfg = get_config("recurrentgemma-9b").reduced()
    key = jax.random.PRNGKey(3)
    params = init_params(key, cfg)
    S = cfg.hybrid.window + 24
    batch = _batch(cfg, key, B=1, S=S)
    logits, _ = forward(params, batch, cfg)
    # perturb a token far outside the window of the last position
    t2 = batch["tokens"].at[0, 0].set((batch["tokens"][0, 0] + 7) % cfg.vocab)
    batch2 = dict(batch, tokens=t2)
    logits2, _ = forward(params, batch2, cfg)
    # recurrent layers DO carry long-range state, so only check that the
    # window-attention code path executes over >window sequences
    assert logits.shape == logits2.shape == (1, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits2)))


def test_param_counts_roughly_match_model_cards():
    """param_count() should land near the published sizes (within 40% —
    it is used only for roofline MODEL_FLOPS)."""
    expect = {
        "qwen2-vl-72b": 72e9, "phi3-medium-14b": 14e9,
        "grok-1-314b": 314e9, "qwen1.5-110b": 111e9,
        "deepseek-67b": 67e9, "qwen2-1.5b": 1.5e9,
        "deepseek-v2-236b": 236e9, "mamba2-370m": 370e6,
        "recurrentgemma-9b": 9e9,
    }
    for arch, want in expect.items():
        got = get_config(arch).param_count()
        assert 0.6 * want < got < 1.6 * want, \
            f"{arch}: {got / 1e9:.1f}B vs expected {want / 1e9:.1f}B"


# ---------------------------------------------------------------------------
# graph-IR transformer block vs the plain-jax layers reference
# ---------------------------------------------------------------------------

def _block_fixture(arch, *, B=2, S=8, seed=0):
    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    return cfg, rng, ids, labels


@pytest.mark.parametrize("arch,par", [
    ("qwen2_1_5b", dict(dp=2, tp=2, pp=1)),   # GQA + qkv bias + tied head
    ("llama_32b", dict(dp=1, tp=2, pp=2)),    # untied head, 2 pp stages
])
def test_graph_block_fwd_bwd_matches_layers_reference(arch, par):
    """The graph-IR block under a sharded TP x DP x PP strategy trains
    to the SAME loss and gradients as the unsharded plain-jax
    ``models.layers`` stack (float tolerance; the key-bias gradient is
    mathematically zero — softmax is shift-invariant along the key
    axis — so comparisons need the absolute floor, not pure rtol)."""
    from repro import api
    from repro.models.graph_block import (block_program, init_block_weights,
                                          reference_loss)

    cfg, rng, ids, labels = _block_fixture(arch)
    prog = block_program(cfg, batch=2, seq=8, **par)
    ws = init_block_weights(prog, rng)

    sess = api.Session(prog, 0, executor=api.SimulatorExecutor())
    sess.load(ws)
    r = sess.train_step({"ids": ids, "labels": labels},
                        num_microbatches=1)

    loss = reference_loss(cfg, cfg.n_layers, ids, labels)
    want, grads = jax.value_and_grad(loss)(
        {n: jnp.asarray(v) for n, v in ws.items()})
    np.testing.assert_allclose(r.loss, float(want), rtol=1e-5, atol=1e-9)
    for n in ws:
        np.testing.assert_allclose(
            r.grad_value(n), np.asarray(grads[n]), atol=1e-6, rtol=2e-4,
            err_msg=f"{arch} grad {n}")


def test_graph_block_single_device_jax_matches_reference():
    """Same differential on the real JaxExecutor (single device, so it
    runs in-process without forced host devices)."""
    from repro import api
    from repro.models.graph_block import (block_program, init_block_weights,
                                          reference_loss)

    cfg, rng, ids, labels = _block_fixture("qwen2_1_5b", seed=1)
    prog = block_program(cfg, batch=2, seq=8, dp=1, tp=1, pp=1)
    ws = init_block_weights(prog, rng)

    sess = api.Session(prog, 0, executor=api.JaxExecutor())
    sess.load(ws)
    r = sess.train_step({"ids": ids, "labels": labels},
                        num_microbatches=1)

    loss = reference_loss(cfg, cfg.n_layers, ids, labels)
    want, grads = jax.value_and_grad(loss)(
        {n: jnp.asarray(v) for n, v in ws.items()})
    np.testing.assert_allclose(r.loss, float(want), rtol=1e-5, atol=1e-9)
    for n in ws:
        np.testing.assert_allclose(
            r.grad_value(n), np.asarray(grads[n]), atol=1e-6, rtol=2e-4,
            err_msg=f"grad {n}")
