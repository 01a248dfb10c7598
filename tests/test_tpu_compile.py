"""Compile the main training path for a TPU v5e that is described, not
attached.

The TPU compiler is installed with JAX, so a ``v5e:2x2`` topology can be
described on any host and programs compiled for it from shapes alone:
what the chip's compiler refuses (a kernel it cannot tile, a step that
does not fit 16 GB, a collective it cannot partition) fails here.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  The tests skip only where the TPU compiler
(``libtpu``) is not installed; any other failure to describe the
topology fails them.  The persistent compile cache is off around them: an entry
compiled for a described chip cannot be read back without one.
"""

import os

import numpy as np
import pytest

#: qwen2-1.5b widths (arXiv:2407.10671): 12 query heads over 2 KV heads
H, K, HD = 12, 2, 128
HBM_BYTES = 16e9        # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    pytest.importorskip("libtpu", reason="the TPU compiler is not "
                                         "installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def qwen():
    from repro.configs import get_config

    return get_config("qwen2-1.5b")


def _mesh(topo, n):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:n]), ("dev",))


def _compile_train_step(topo, cfg, *, dp, tp, batch, seq):
    """The graph-IR train step of ``cfg`` (1 layer) as ``Session.
    train_step`` would run it, compiled from ShapeDtypeStructs on the
    first ``dp * tp`` described chips."""
    from repro import api
    from repro.models.graph_block import block_program

    prog = block_program(cfg, batch=batch, seq=seq, n_layers=1, dp=dp,
                         tp=tp, pp=1)
    tplan = prog.compile_train(0)
    ex = api.JaxExecutor(mesh=_mesh(topo, dp * tp))
    lw = ex.lowered(tplan, tplan.train_fetches)
    dtypes = {t.name: np.int32 if t.name in ("ids", "labels")
              else np.float32 for t in lw.leaves}
    return lw, lw.lower(dtypes).compile()


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_flash_kernel_compiles_at_qwen2_widths(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.flash_attention import flash_attention

    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, H, 1024, HD), jnp.float32, sharding=one)
    kv = jax.ShapeDtypeStruct((2, K, 1024, HD), jnp.float32, sharding=one)
    compiled = flash_attention.lower(q, kv, kv, causal=True,
                                     interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("setting", ["default", "tensorfloat32",
                                     "highest", "BF16_BF16_F32"])
def test_flash_kernel_compiles_under_each_matmul_precision(topo, setting):
    """Mosaic contracts at one pass or at fp32 only; a setting between
    the two ("tensorfloat32", three passes in XLA) must still lower, and
    so must a dot-algorithm preset, which Mosaic cannot take."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.flash_attention import flash_attention

    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 2, 256, HD), jnp.float32, sharding=one)
    with jax.default_matmul_precision(setting):
        compiled = flash_attention.lower(q, q, q, causal=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_train_step_compiles_with_the_kernel(topo, qwen):
    """The mesh's platform, not the process's CPU backend, picks the
    attention kernel: the step placed on a TPU holds it compiled."""
    lw, compiled = _compile_train_step(topo, qwen, dp=1, tp=1, batch=1,
                                       seq=256)
    assert lw.stats.pallas_dispatches > 0 and lw.stats.ref_dispatches == 0
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_dp2tp2_train_step_compiles_on_four_chips(topo, qwen):
    lw, compiled = _compile_train_step(topo, qwen, dp=2, tp=2, batch=2,
                                       seq=256)
    text = compiled.as_text()
    assert lw.stats.grouped_reduces > 0
    # subgroup all-reduces: the TP partial sums reduce within pairs
    assert "all-reduce" in text and "replica_groups={{0,1},{2,3}}" in text
    assert "tpu_custom_call" in text
    assert _total_bytes(compiled) < HBM_BYTES
