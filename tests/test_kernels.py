"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles.

Shapes and dtypes are swept per the deliverable spec; tolerances scale
with dtype.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref, rglru_ref, ssd_scan_ref
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[dtype]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kh,s,d", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA 2:1
    (1, 8, 1, 128, 128),    # MQA, MXU-aligned head dim
    (1, 2, 2, 384, 32),     # non-pow2 seq (3 blocks of 128)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(dtype, b, h, kh, s, d, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kh, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kh, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=1e-2)


def test_flash_attention_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    for window in (64, 128, 256):
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-3)


def test_flash_attention_block_shape_invariance():
    """Different BlockSpec tilings must agree (tile-boundary bugs)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    o1 = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    o2 = flash_attention(q, k, v, block_q=64, block_k=128, interpret=True)
    o3 = flash_attention(q, k, v, block_q=128, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o3), atol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan (Mamba2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 2, 64, 128, 64),
    (2, 256, 4, 64, 128, 128),
    (1, 192, 2, 32, 64, 64),      # 3 chunks, small head/state
])
def test_ssd_scan_sweep(dtype, b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, s, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, s, n)) * 0.3).astype(dtype)
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, str_ = ssd_scan_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=_tol(dtype) * 10, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                               atol=_tol(dtype) * 10, rtol=5e-2)


def test_ssd_chunk_invariance():
    """The scan must be exactly chunk-size independent."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, p, n = 1, 256, 2, 32, 64
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, n)) * 0.3
    y64, _ = ssd_scan(x, dt, A, B, C, chunk=64, interpret=True)
    y128, _ = ssd_scan(x, dt, A, B, C, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y64), np.asarray(y128),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,w,chunk", [
    (1, 128, 128, 64),
    (2, 256, 256, 128),
    (1, 384, 128, 128),
])
def test_rglru_sweep(dtype, b, s, w, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = (jax.random.normal(ks[0], (b, s, w)) * 0.5).astype(dtype)
    r = jax.nn.sigmoid(jax.random.normal(ks[1], (b, s, w))).astype(dtype)
    i = jax.nn.sigmoid(jax.random.normal(ks[2], (b, s, w))).astype(dtype)
    lam = jax.random.normal(ks[3], (w,)) * 0.5
    y = rglru_pallas(x, r, i, lam, chunk=chunk, interpret=True)
    yr = rglru_ref(x, r, i, lam)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=_tol(dtype) * 5, rtol=3e-2)


def test_rglru_matches_stepwise_decode():
    """Kernel scan == the model's one-step decode recurrence."""
    from repro.models.rglru import rglru_decode_step
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    b, s, w = 1, 32, 128
    x = jax.random.normal(ks[0], (b, s, w)) * 0.5
    r = jax.nn.sigmoid(jax.random.normal(ks[1], (b, s, w)))
    i = jax.nn.sigmoid(jax.random.normal(ks[2], (b, s, w)))
    lam = jax.random.normal(ks[3], (w,)) * 0.5
    y = rglru_pallas(x, r, i, lam, chunk=32, interpret=True)
    h = jnp.zeros((b, w))
    outs = []
    for t in range(s):
        yt, h = rglru_decode_step(x[:, t:t + 1], r[:, t:t + 1],
                                  i[:, t:t + 1], lam, h)
        outs.append(yt[:, 0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(outs, 1)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# dispatch: the two implementations an attention op can lower onto
# ---------------------------------------------------------------------------

def test_ops_dispatch_ref_vs_pallas():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    a = flash_attention_ref(q, k, v)
    b = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("setting,fp32", [
    (None, False), ("default", False), ("bfloat16", False),
    ("highest", True), ("float32", True), ("tensorfloat32", True),
    ("BF16_BF16_F32", False), ("ANY_F8_ANY_F8_F32", False),
    ("BF16_BF16_F32_X6", True), ("TF32_TF32_F32", True),
    ("F32_F32_F32", True),
])
def test_flash_kernel_follows_default_matmul_precision(setting, fp32):
    """f32 inputs contract as the caller's matmul precision asks: one
    pass by default, fp32 when more than one pass is asked for (Mosaic
    offers those two); other dtypes always take one pass.  The choice is
    explicit, so no preset reaches Mosaic."""
    from repro.kernels.flash_attention import contract_precision

    P = jax.lax.Precision
    with jax.default_matmul_precision(setting):
        assert contract_precision(jnp.float32) == (
            P.HIGHEST if fp32 else P.DEFAULT)
        assert contract_precision(jnp.bfloat16) == P.DEFAULT


def test_set_policy_rejects_unknown_policy():
    """Regression: set_policy validated with a bare assert (stripped
    under ``python -O``); it must raise ValueError naming the valid
    policies."""
    from repro.kernels import policy

    with pytest.raises(ValueError, match="auto, pallas, ref"):
        policy.set_policy("fast")
    assert policy.get_policy() == "auto"  # unchanged on rejection
    policy.set_policy("ref")
    try:
        assert policy.get_policy() == "ref"
    finally:
        policy.set_policy("auto")


def test_select_attention_impl_honours_policy_and_eligibility():
    from repro.kernels import policy

    ok_q, ok_kv = (1, 4, 128, 64), (1, 2, 128, 64)
    bad_q, bad_kv = (1, 4, 128, 60), (1, 2, 128, 60)  # d % 8 != 0
    policy.set_policy("pallas")
    try:
        assert policy.select_attention_impl(ok_q, ok_kv) == "pallas"
        assert policy.select_attention_impl(bad_q, bad_kv) == "ref"
    finally:
        policy.set_policy("auto")
    # ref policy forces the reference even for eligible shards
    policy.set_policy("ref")
    try:
        assert policy.select_attention_impl(ok_q, ok_kv) == "ref"
    finally:
        policy.set_policy("auto")


@pytest.mark.parametrize("platform,want", [("tpu", "pallas"),
                                           ("cpu", "ref")])
def test_auto_policy_follows_the_mesh_platform(platform, want):
    """Under "auto" the platform of the devices a program is placed on
    picks the kernel, whatever backend the process defaults to."""
    from repro.kernels import policy

    ok_q, ok_kv = (1, 4, 128, 64), (1, 2, 128, 64)
    assert policy.get_policy() == "auto"
    assert policy.select_attention_impl_per_class(ok_q, ok_kv,
                                                  platform) == want
    # ineligible shards stay on the reference on every platform
    assert policy.select_attention_impl_per_class(
        (1, 4, 128, 60), (1, 2, 128, 60), platform) == "ref"
