"""The benchmark's plain reference at CPU sizes, for every configuration
in ``bench/configs``: its parameters and its loss and gradients against
the program's own train step; the dense architecture's also against the
program's twin (``graph_block.reference_loss``); and its AdamW against
the program's."""

import numpy as np
import pytest
from tiny_cell import CONFIGS, config_arch, tiny_model

import jax
import jax.numpy as jnp

from bench import reference, run

DENSE = [c for c in CONFIGS if config_arch(c)[0]["arch"] == "dense"]
LAYOUT = {"dp": 1, "tp": 1}


def _arch(config):
    return config_arch(config)[1]


def _batch(m, seed=3, batch=2, seq=16):
    f = run.feeds(seed, 0, batch, seq, m["vocab_size"])
    return jnp.asarray(f["ids"]), jnp.asarray(f["labels"])


def _close(grads, want):
    # a key's bias has no gradient in exact arithmetic (softmax ignores
    # a shift along the keys): judge every leaf against the largest
    scale = max(float(np.abs(g).max()) for g in want.values())
    for name, g in want.items():
        np.testing.assert_allclose(grads[name], g, rtol=1e-5,
                                   atol=1e-6 * scale, err_msg=name)


@pytest.mark.parametrize("config", CONFIGS)
def test_parameters_are_the_programs(config):
    m, arch = tiny_model(config), _arch(config)
    prog = arch.program(m, 2, 16, LAYOUT)
    program = [(t.name, tuple(t.shape)) for t in prog.graph.parameters()]
    assert program == list(arch.param_shapes(m).items())


@pytest.mark.parametrize("config", DENSE)
def test_loss_and_grads_match_graph_block_reference(config):
    from repro.kernels import policy
    from repro.models.graph_block import reference_loss

    m, arch = tiny_model(config), _arch(config)
    params = reference.weight_maker(arch, m)(
        *reference.seed_words(2**31 + 5))
    ids, labels = _batch(m)
    with jax.default_matmul_precision("highest"):
        loss, grads = reference.loss_and_grad(arch, m)(params, ids, labels)
        before = policy.get_policy()
        policy.set_policy("ref")
        try:
            want, want_g = jax.value_and_grad(reference_loss(
                arch.model_config(m), m["num_hidden_layers"], ids,
                labels))(params)
        finally:
            policy.set_policy(before)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    _close(grads, want_g)


@pytest.mark.parametrize("config", CONFIGS)
def test_loss_and_grads_match_the_program(config):
    """The program's own train step, built by the architecture module
    and run as the benchmark runs it, against the reference."""
    from jax.sharding import Mesh
    from repro import api
    from repro.optim.adamw import AdamWConfig

    m, arch = tiny_model(config), _arch(config)
    params = reference.weight_maker(arch, m)(
        *reference.seed_words(2**31 + 6))
    ids, labels = _batch(m, seed=4)
    with jax.default_matmul_precision("highest"):
        sess = api.Session(
            arch.program(m, 2, 16, LAYOUT), 0,
            executor=api.JaxExecutor(mesh=Mesh(
                np.array(jax.devices()[:1]), ("dev",))),
            optimizer=AdamWConfig())
        sess.load(jax.device_get(params))
        out = sess.train_step({"ids": np.asarray(ids),
                               "labels": np.asarray(labels)})
        loss, grads = reference.loss_and_grad(arch, m)(params, ids, labels)
    assert set(out.grads) == set(params)
    assert out.loss == pytest.approx(float(loss), rel=1e-6)
    _close({n: out.grad_value(n) for n in params},
           {n: np.asarray(g) for n, g in grads.items()})


def test_adamw_matches_the_programs_optimizer():
    from repro.optim.adamw import AdamWConfig, apply_updates, \
        init_opt_state

    cell = run.load_cell("qwen2-1.5b.b4s1024")
    opt, arch = cell["traffic_mix"]["optimizer"], cell["arch"]
    m = tiny_model("qwen2-1.5b")
    params = reference.weight_maker(arch, m)(*reference.seed_words(11))
    ids, labels = _batch(m)
    _, grads = reference.loss_and_grad(arch, m)(params, ids, labels)
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
    m, arch = tiny_model("phi3-medium-14b"), _arch("phi3-medium-14b")
    make = reference.weight_maker(arch, m)
    a = make(*reference.seed_words(2**33 + 1))
    b = make(*reference.seed_words(2**33 + 1))
    c = make(*reference.seed_words(1))
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert not np.array_equal(a["l0/wq"], c["l0/wq"])
    assert np.all(np.asarray(a["final_norm"]) == 1.0)
