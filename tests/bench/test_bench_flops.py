"""Operation counts of the architecture modules and of bench/flops.py
against hand counts, and against the work the reference does."""

import numpy as np
import pytest
from tiny_cell import CONFIGS, config_arch, tiny_model

from bench import flops, reference

# qwen2-1.5b, 4 layers: per layer 1536*1536 (q) + 2*1536*256 (k, v)
# + 1536*1536 (o) + 3*1536*8960 (gate, up, down) = 46,792,704; the tied
# head 1536*151936 = 233,373,696.  phi3-medium-14b, 2 layers: per layer
# 2*5120*5120 + 2*5120*1280 + 3*5120*17920 = 340,787,200; head
# 5120*32064 = 164,167,680.
HAND = {
    "qwen2-1.5b": (4 * 46_792_704 + 233_373_696,
                   6 * (4 * 46_792_704 + 233_373_696)
                   + 4 * 6 * 1024 * 12 * 128),
    "phi3-medium-14b": (2 * 340_787_200 + 164_167_680,
                        6 * (2 * 340_787_200 + 164_167_680)
                        + 2 * 6 * 1024 * 40 * 128),
}
DENSE = [c for c in CONFIGS if config_arch(c)[0]["arch"] == "dense"]


@pytest.mark.parametrize("config", sorted(HAND))
def test_train_flops_match_hand_counts(config):
    m, arch = config_arch(config)
    params, per_token = HAND[config]
    assert arch.matmul_params(m) == params
    assert arch.train_flops_per_token(m, 1024) == per_token


@pytest.mark.parametrize("config", DENSE)
def test_matmul_params_are_the_reference_matrices(config):
    """Every 2-D weight but the embedding table is a matmul a token
    meets once; a tied head is the table itself."""
    m, arch = config_arch(config)
    shapes = arch.param_shapes(m)
    mats = sum(s[0] * s[1] for n, s in shapes.items()
               if len(s) == 2 and n != "embed")
    if m["tie_word_embeddings"]:
        mats += m["hidden_size"] * m["vocab_size"]
    assert arch.matmul_params(m) == mats


@pytest.mark.parametrize("config", CONFIGS)
def test_train_flops_are_no_more_than_the_reference_computes(config):
    """A step's counted work is at most what XLA counts in the plain
    reference's loss and gradient, which computes every score of the
    causal mask and its elementwise work too: a count above it would
    put ``mfu`` over what the chip did."""
    import jax
    import jax.numpy as jnp

    m, arch = tiny_model(config), config_arch(config)[1]
    params = jax.eval_shape(reference.weight_maker(arch, m),
                            np.uint32(0), np.uint32(0))
    for seq in (16, 64):
        ids = jax.ShapeDtypeStruct((2, seq), jnp.int32)
        cost = reference.loss_and_grad(arch, m).lower(
            params, ids, ids).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        counted = arch.train_flops_per_token(m, seq) * 2 * seq
        assert 0 < counted <= cost["flops"], (seq, counted, cost["flops"])


def test_flash_attention_counts_by_hand():
    # seq 4: 4*5/2 = 10 visible pairs; 2 matmuls of 2*hd ops per pair
    ops, nbytes = flops.flash_attention(1, 1, 1, 4, 2)
    assert ops == 4 * 10 * 2
    # q and out (1*1*4*2 each), k and v (1*1*4*2 each), float32
    assert nbytes == 4 * (2 * 8 + 2 * 8)
    ops, nbytes = flops.flash_attention(4, 12, 2, 1024, 128)
    assert ops == 4 * 4 * 12 * (1024 * 1025 / 2) * 128
    assert nbytes == 4 * (2 * 4 * 12 * 1024 * 128 + 2 * 4 * 2 * 1024 * 128)


def test_flash_attention_window_counts_by_hand():
    # seq 6, window 2: queries see 1, 2, 2, 2, 2, 2 keys = 11 pairs
    ops, nbytes = flops.flash_attention(1, 1, 1, 6, 2, window=2)
    assert ops == 4 * 11 * 2
    assert nbytes == flops.flash_attention(1, 1, 1, 6, 2)[1]
    # a window as long as the sequence is full causal attention
    assert flops.flash_attention(2, 4, 2, 64, 16, window=64) == \
        flops.flash_attention(2, 4, 2, 64, 16)
    # S 4096 under a 1024 window: 524,800 + 3072 * 1024 pairs against
    # 4096 * 4097 / 2 unwindowed, 2.29x fewer
    full = flops.flash_attention(1, 1, 1, 4096, 128)[0]
    windowed = flops.flash_attention(1, 1, 1, 4096, 128, window=1024)[0]
    assert windowed == 4 * (524_800 + 3072 * 1024) * 128
    assert full / windowed == pytest.approx(2.2859, abs=1e-4)
