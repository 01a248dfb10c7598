"""The benchmark's plain reference pinned to the program's own twin
(``graph_block.reference_loss``) and to its AdamW, at CPU sizes."""

import numpy as np
import pytest
from tiny_cell import tiny_model

import jax
import jax.numpy as jnp

from bench import reference, run

CONFIGS = ["qwen2-1.5b", "phi3-medium-14b"]


def _batch(m, seed=3, batch=2, seq=16):
    f = run.feeds(seed, 0, batch, seq, m["vocab_size"])
    return jnp.asarray(f["ids"]), jnp.asarray(f["labels"])


@pytest.mark.parametrize("config", CONFIGS)
def test_parameters_are_the_programs(config):
    from repro.models.graph_block import block_program

    m = tiny_model(config)
    prog = block_program(run.program_config(m), batch=2, seq=16,
                         n_layers=m["num_hidden_layers"])
    program = {t.name: tuple(t.shape) for t in prog.graph.parameters()}
    assert program == reference.param_shapes(m)


@pytest.mark.parametrize("config", CONFIGS)
def test_loss_and_grads_match_graph_block_reference(config):
    from repro.kernels import policy
    from repro.models.graph_block import reference_loss

    m = tiny_model(config)
    params = reference.weight_maker(m)(*reference.seed_words(2**31 + 5))
    ids, labels = _batch(m)
    with jax.default_matmul_precision("highest"):
        loss, grads = reference.loss_and_grad(m)(params, ids, labels)
        before = policy.get_policy()
        policy.set_policy("ref")
        try:
            want, want_g = jax.value_and_grad(reference_loss(
                run.program_config(m), m["num_hidden_layers"], ids,
                labels))(params)
        finally:
            policy.set_policy(before)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    # a key's bias has no gradient in exact arithmetic (softmax ignores
    # a shift along the keys): judge every leaf against the largest
    scale = max(float(jnp.abs(g).max()) for g in want_g.values())
    for name, g in want_g.items():
        np.testing.assert_allclose(grads[name], g, rtol=1e-5,
                                   atol=1e-6 * scale, err_msg=name)


def test_adamw_matches_the_programs_optimizer():
    from repro.optim.adamw import AdamWConfig, apply_updates, \
        init_opt_state

    opt = run.load_cell("qwen2-1.5b.b4s1024")["traffic_mix"]["optimizer"]
    m = tiny_model("qwen2-1.5b")
    params = reference.weight_maker(m)(*reference.seed_words(11))
    ids, labels = _batch(m)
    _, grads = reference.loss_and_grad(m)(params, ids, labels)
    clip, update = reference.adamw(opt)
    scale = clip(grads)
    want, state, _ = apply_updates(params, grads, init_opt_state(params),
                                   AdamWConfig(**opt))
    for name, p in params.items():
        zero = jnp.zeros_like(p)
        mine, mom, _, clipped = update(p, grads[name], zero, zero, scale,
                                       np.int32(1))
        np.testing.assert_allclose(mine, want[name], rtol=1e-6, atol=1e-9,
                                   err_msg=name)
        np.testing.assert_allclose(mom, state["m"][name], rtol=1e-6,
                                   atol=1e-12, err_msg=name)
        np.testing.assert_allclose(clipped, grads[name], rtol=1e-6)


def test_weights_follow_the_seed():
    m = tiny_model("phi3-medium-14b")
    make = reference.weight_maker(m)
    a = make(*reference.seed_words(2**33 + 1))
    b = make(*reference.seed_words(2**33 + 1))
    c = make(*reference.seed_words(1))
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert not np.array_equal(a["l0/wq"], c["l0/wq"])
    assert np.all(np.asarray(a["final_norm"]) == 1.0)
