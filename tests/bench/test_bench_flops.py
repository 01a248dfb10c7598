"""Operation counts of bench/flops.py against hand counts."""

import pytest
from tiny_cell import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import flops, reference, run

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


@pytest.mark.parametrize("config", sorted(HAND))
def test_train_flops_match_hand_counts(config):
    m = run.read_json(f"{ROOT}/bench/configs/{config}.json")
    params, per_token = HAND[config]
    assert flops.matmul_params(m) == params
    assert flops.train_flops_per_token(m, 1024) == per_token


@pytest.mark.parametrize("config", sorted(HAND))
def test_matmul_params_are_the_reference_matrices(config):
    """Every 2-D weight but the embedding table is a matmul a token
    meets once; a tied head is the table itself."""
    m = run.read_json(f"{ROOT}/bench/configs/{config}.json")
    shapes = reference.param_shapes(m)
    mats = sum(s[0] * s[1] for n, s in shapes.items()
               if len(s) == 2 and n != "embed")
    if m["tie_word_embeddings"]:
        mats += m["hidden_size"] * m["vocab_size"]
    assert flops.matmul_params(m) == mats


def test_flash_attention_counts_by_hand():
    # seq 4: 4*5/2 = 10 visible pairs; 2 matmuls of 2*hd ops per pair
    ops, nbytes = flops.flash_attention(1, 1, 1, 4, 2)
    assert ops == 4 * 10 * 2
    # q and out (1*1*4*2 each), k and v (1*1*4*2 each), float32
    assert nbytes == 4 * (2 * 8 + 2 * 8)
    ops, nbytes = flops.flash_attention(4, 12, 2, 1024, 128)
    assert ops == 4 * 4 * 12 * (1024 * 1025 / 2) * 128
    assert nbytes == 4 * (2 * 4 * 12 * 1024 * 128 + 2 * 4 * 2 * 1024 * 128)
