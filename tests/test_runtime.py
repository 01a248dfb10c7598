"""Execution-backend tests: simulator <-> shard_map differential equivalence.

The heavy sweep runs ONCE in a subprocess with 8 forced host CPU devices
(``repro.runtime.selftest``, keeping this process at its default device
count per the dry-run spec); the parametrized tests then assert each
case's bit-exact verdict from the machine-readable report.  Cheap
single-device and pure-planning paths run in-process.
"""

import json

import numpy as np
import pytest

from repro.core.annotations import DS, DUP, HSPMD, PARTIAL, spmd

KINDS = ["ID", "SR", "AR", "RS", "AG", "SplitAR", "SplitRS", "SplitAG",
         "BSR", "Slice"]
NDEVS = [2, 4, 8]


@pytest.fixture(scope="module")
def report():
    from repro.runtime.harness import run_subprocess
    proc = run_subprocess("repro.runtime.selftest", n_devices=8)
    for line in proc.stdout.splitlines():
        if line.startswith("RUNTIME_SELFTEST_JSON "):
            return json.loads(line[len("RUNTIME_SELFTEST_JSON "):])
    pytest.fail(f"selftest produced no report (rc={proc.returncode}):\n"
                f"{proc.stdout}\n{proc.stderr}")


def _case(report, key):
    case = report["cases"].get(key)
    assert case is not None, f"selftest never ran case {key}"
    assert case["ok"], f"{key}: {case.get('error')}\n{case.get('trace', '')}"
    return case


@pytest.mark.parametrize("ndev", NDEVS)
@pytest.mark.parametrize("kind", KINDS)
def test_commstep_kind_matches_simulator(report, kind, ndev):
    """Every CommStep kind executes under shard_map on real devices and is
    bit-exact against simulator.apply_plan."""
    case = _case(report, f"{kind}/{ndev}")
    assert kind in case["step_kinds"], case


@pytest.mark.parametrize("kind", ["AR", "RS", "SplitAR", "SplitRS"])
def test_fast_psum_reduction_path(report, kind):
    """The native-dtype psum path is exact for order-insensitive shards."""
    _case(report, f"fast:{kind}/8")


def test_heterogeneous_hsplits_bsr(report):
    assert _case(report, "hetero:hsplits/4")["plan_kind"] == "fallback:BSR"


def test_fig9_multistep_stage(report):
    """The paper's Fig 9 CommOp id=2 (RS on {0,3}, BSR toward {5,6}, ID on
    {1}) runs as ONE stage of parallel steps on real devices."""
    case = _case(report, "hetero:fig9/7")
    assert case["plan_kind"] == "bottom:BSR+ID+RS"
    assert set(case["step_kinds"]) == {"RS", "BSR"}


@pytest.mark.parametrize("ndev", NDEVS)
def test_resharding_roundtrip(report, ndev):
    """src -> dst -> src on real devices restores every shard exactly."""
    _case(report, f"roundtrip:split/{ndev}")


def test_resharding_roundtrip_hetero(report):
    _case(report, "roundtrip:hetero/4")


def test_switch_migration_jax_backend(report):
    """execute_switch(backend="jax") migrates weights through the fused-BSR
    path on real devices: exact dst shards, bit-equal to the simulator
    backend, and reversible."""
    _case(report, "switch:jax/8")


@pytest.mark.parametrize("ndev", NDEVS)
def test_api_session_executor_parity(report, ndev):
    """repro.api acceptance: Session.run on JaxExecutor executes a
    specialized pipeline stage's compute + comm ExecItems end-to-end under
    shard_map, bit-exact against SimulatorExecutor."""
    case = _case(report, f"api:session/{ndev}")
    assert case["devices"] == ndev


@pytest.mark.parametrize("ndev", NDEVS)
def test_api_pipeline_schedule_parity(report, ndev):
    """Microbatched pipeline acceptance: Session.run(num_microbatches=m)
    is bit-exact sim vs jax (one scanned shard_map program) per
    microbatch, bit-identical across m in {1,2,4} for the accumulated
    loss, GPipe == 1F1B bitwise, timetable matching the analytic
    (m + s - 1) fill/drain count."""
    case = _case(report, f"api:pipeline/{ndev}")
    assert case["n_stages"] == 2
    assert case["slots"] == 2 * (4 + case["n_stages"] - 1)


@pytest.mark.parametrize("ndev", NDEVS)
def test_api_interleaved_schedule_parity(report, ndev):
    """Interleaved virtual-stage acceptance: a v=2 zigzag plan
    (s0 -> s1 -> s0 -> s1) runs Megatron's interleaved timetable on the
    simulator and ONE scanned shard_map program on jax — bit-exact per
    microbatch shard, bit-identical outputs across m in {1,2,4}, flat
    1F1B/GPipe rejected, and the lowered jax program deduces the same
    S*v=4 virtual-stage structure."""
    case = _case(report, f"api:pipeline/interleaved{ndev}")
    assert case["v"] == 2
    assert 0.0 <= case["bubble_fraction"] < 1.0


@pytest.mark.parametrize("ndev", NDEVS)
def test_api_train_step_bit_exact(report, ndev):
    """End-to-end TRAINING regression on the specialization-class
    lowering: losses, gradient shards and updated weight shards
    bit-exact sim vs jax and bit-identical across m x {1f1b, gpipe}
    (integer leaves) — the segment/class emission on the jax side and
    the class-vectorized numpy dispatch on the sim side must agree to
    the last bit."""
    case = _case(report, f"api:train/{ndev}")
    assert np.isfinite(case["loss"])


@pytest.mark.parametrize("ndev", NDEVS)
def test_api_train_interleaved_bit_exact(report, ndev):
    """Interleaved (v=2 zigzag) training: bit-exact sim vs jax and
    across m in {1,2,4} on the refactored path — covers segments whose
    participant classes alternate between the two device halves."""
    _case(report, f"api:train/interleaved{ndev}")


def test_api_train_hetero_bit_exact(report):
    """hsize=2 training (two specialization classes per segment): the
    two-tier grad reduction still resolves and executes bit-exact."""
    case = _case(report, "api:train/hetero4")
    assert "SplitAR" in case["grad_comms"]["W1"]


@pytest.mark.parametrize("ndev", NDEVS)
def test_async_pipeline_bit_exact(report, ndev):
    """Async MPMD executor acceptance: per-(virtual stage, phase) XLA
    programs with double-buffered P2P channels and eager grad-reduce
    stay BITWISE equal to the simulator and the scanned jax program
    across m in {1,2,4} x {1f1b, gpipe, interleaved} — one fwd + one
    bwd program per virtual stage, comm hoisted into channels."""
    case = _case(report, f"async:pipeline/{ndev}")
    assert case["programs"] == 4            # 2 virtual stages x 2 phases
    assert case["channels"] >= 2            # boundary P2P both phases


def test_async_train_bit_exact(report):
    """Async TRAINING: losses, gradient shards and updated weight
    shards bit-exact vs sim and jax across m x {1f1b, gpipe}, plus the
    v=2 interleaved zigzag (per-chunk programs on one device)."""
    case = _case(report, "async:train/4")
    assert np.isfinite(case["loss"])
    assert np.isfinite(case["zigzag_loss"])


def test_search_validation_bit_exact_and_concordant(report):
    """The automated strategy search's execution validation: the top-3
    candidates for the 2-fast + 2-slow CPU fixture train bit-exact sim
    vs jax, the winner is a heterogeneous (hsize>1) candidate, and the
    speed-projected measured ordering agrees with the cost model's."""
    case = _case(report, "search:hetero/4")
    assert case["winner"].startswith("het"), case
    assert case["agreement"] >= 2 / 3, case


@pytest.mark.parametrize("key, want_kinds", [
    ("elastic:trace/4to2", ["shrink", "class-change"]),
    ("elastic:trace/2to4", ["grow", "class-change"]),
    ("elastic:trace/hetero", ["class-change", "shrink"]),
])
def test_elastic_trace_bit_exact(report, key, want_kinds):
    """The elastic trace driver: real train_steps through device
    loss/join, weights + AdamW m/v migrated restart-free — the whole
    trajectory bitwise equal sim vs jax AND to an uninterrupted
    single-strategy reference run."""
    case = _case(report, key)
    assert case["kinds"] == want_kinds, case


def test_grouped_reduce_collectives(report):
    """Reduce groups lower onto axis_index_groups subgroup collectives
    (SplitAR's cross-subgroup groups), bit-exact vs the simulator."""
    case = _case(report, "grouped:reduce/4")
    assert case["grouped"] == case["reduce_groups"] > 0


def test_ppermute_fusion_reduces_launches(report):
    """Per-(src,dst) ppermute pairs are fused into batched permutes: the
    AG/8 multicast lowers to strictly fewer collective launches than
    point-to-point pairs, same bits (the kind sweep re-proves exactness)."""
    case = _case(report, "fusion:stats/8")
    assert case["ppermute_calls"] < case["copy_pairs"], case


# ---------------------------------------------------------------------------
# in-process paths (single device / pure planning)
# ---------------------------------------------------------------------------

def test_execute_plan_single_device_identity():
    from repro.core.comm_resolve import resolve
    from repro.launch.mesh import make_runtime_mesh
    from repro.runtime import execute_plan

    a = spmd([0], DS({}))
    value = np.arange(12.0, dtype=np.float32).reshape(3, 4)
    plan = resolve(a, a, value.shape)
    out = execute_plan(plan, {0: value}, value.shape, make_runtime_mesh(1))
    np.testing.assert_array_equal(out[0], value)


def test_execute_plan_rejects_bad_shard_shape():
    from repro.core.comm_resolve import resolve
    from repro.launch.mesh import make_runtime_mesh
    from repro.runtime import execute_plan

    a = spmd([0], DS({}))
    plan = resolve(a, a, (3, 4))
    with pytest.raises(ValueError, match="shard shape"):
        execute_plan(plan, {0: np.zeros((4, 4), np.float32)}, (3, 4),
                     make_runtime_mesh(1))


def test_host_device_env_holds_children_to_cpu():
    """A forced-host-device child never reaches for a chip its parent
    may hold: its environment pins the CPU backend."""
    from repro.runtime.harness import FORCE_FLAG, host_device_env

    env = host_device_env(4, base={"JAX_PLATFORMS": "tpu",
                                   "XLA_FLAGS": f"{FORCE_FLAG}=2 --x"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].split() == ["--x", f"{FORCE_FLAG}=4"]


@pytest.mark.parametrize("platform,advice", [
    ("cpu", "--xla_force_host_platform_device_count=4"),
    ("tpu", "use a layout of at most 1 device(s)"),
])
def test_device_shortfall_names_the_fix_per_platform(platform, advice):
    from repro.launch.mesh import device_shortfall

    msg = device_shortfall("graph", 4, 1, platform)
    assert f"only 1 {platform} device(s)" in msg and advice in msg


def test_lowered_graph_on_too_small_mesh_names_the_shortfall():
    from repro.configs import get_config
    from repro.launch.mesh import make_runtime_mesh
    from repro.models.graph_block import block_program
    from repro.runtime.program import LoweredGraph

    cfg = get_config("qwen2-1.5b").reduced()
    tplan = block_program(cfg, batch=2, seq=8, dp=2, tp=1).compile_train(0)
    with pytest.raises(ValueError, match="spans 2 logical devices"):
        LoweredGraph(tplan.graph, mesh=make_runtime_mesh(1))


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_goes_where_the_environment_says(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's cache is written there
    and nowhere else; without it, it goes to the fixed in-checkout
    directory (checked without compiling, so the test leaves the
    checkout clean)."""
    import os
    import subprocess
    import sys

    from repro.runtime.harness import _repo_root, host_device_env

    src = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.harness import use_compile_cache\n"
        "path = use_compile_cache()\n"
        "print(path)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "if path != jax.config.jax_compilation_cache_dir:\n"
        "    raise SystemExit(1)\n"
        f"if {placed}:\n"
        "    jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env = host_device_env(1)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "x")
    proc = subprocess.run([sys.executable, "-c", src], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.split()
    if placed:
        assert lines == [str(tmp_path / "x")] * 2
        assert any((tmp_path / "x").iterdir())
        assert [p.name for p in tmp_path.iterdir()] == ["x"]
    else:
        assert lines == [os.path.join(_repo_root(), ".jax_cache")] * 2
        assert not any(tmp_path.iterdir())


_AOT_REUSE_SRC = """
import jax, numpy as np
from jax.sharding import Mesh
from repro import api
from repro.configs import get_config
from repro.models.graph_block import block_program, init_block_weights

compiled = []
def on_event(event, secs, fun_name='?', **_):
    if event == '/jax/core/compile/backend_compile_duration':
        compiled.append(fun_name)

cfg = get_config('qwen2-1.5b').reduced()
for dp, tp in ((1, 1), (2, 2)):
    prog = block_program(cfg, batch=2, seq=8, n_layers=1, dp=dp, tp=tp,
                         pp=1)
    rng = np.random.default_rng(0)
    ws = init_block_weights(prog, rng)
    feeds = {n: rng.integers(0, cfg.vocab, (2, 8), np.int32)
             for n in ('ids', 'labels')}
    ex = api.JaxExecutor(mesh=Mesh(np.array(jax.devices()[:dp * tp]),
                                   ('dev',)))
    sess = api.Session(prog, 0, executor=ex)
    sess.load(ws)
    tplan = prog.compile_train(0)
    lw = ex.lowered(tplan, tplan.train_fetches)
    lw.lower({t.name: np.int32 if t.name in feeds else np.float32
              for t in lw.leaves}).compile()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    steps = []
    for _ in range(2):
        sess.train_step(dict(feeds))
        steps.append(sorted(compiled))
        compiled.clear()
    jax.monitoring.unregister_event_duration_listener(on_event)
    print(f'dp{dp}tp{tp} x64={lw._x64} compiled={steps}')
"""

#: what the first train step compiles besides the step program: AdamW's
#: zero m/v blocks and its two programs over the mesh (optim/adamw.py)
_OPTIMIZER_PROGRAMS = sorted(["jit(_zero_blocks)", "jit(_row_squares)",
                              "jit(_row_update)"])


def test_ahead_of_time_lowering_is_what_a_step_runs():
    """``LoweredGraph.lower`` places and scopes its arguments as a call
    does, so once it is compiled the train steps compile nothing more:
    the executable an ahead-of-time check reads is the one that runs.
    dp2tp2 takes the exact float64 fold, traced in an x64 scope.  The
    first step compiles the optimizer's programs for the mesh, and no
    step compiles the step program."""
    from repro.runtime.harness import run_subprocess

    proc = run_subprocess(_AOT_REUSE_SRC, n_devices=4, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split("\n")[:2] == [
        f"dp1tp1 x64=False compiled={[_OPTIMIZER_PROGRAMS, []]}",
        f"dp2tp2 x64=True compiled={[_OPTIMIZER_PROGRAMS, []]}"]


def test_device_items_matches_specialize():
    """The runtime's per-device view of a plan lists exactly the comm
    ExecItems progressive specialization gives that device (Fig 9)."""
    from repro.core.graph import Graph
    from repro.core.specialize import resolve_comm_ops, specialize
    from repro.runtime import device_items

    g = Graph()
    x_annot = HSPMD(dgs=[[0, 3], [2, 4], [1]],
                    dss=[DS({2: 2}), DS({0: 2}), DS({})], hdim=0)
    w_dup = HSPMD(dgs=[[0, 3], [2, 4], [1]],
                  dss=[DS({DUP: 2}), DS({DUP: 2}), DS({})], hdim=DUP)
    w_tp = HSPMD(dgs=[[0, 3], [2, 4], [1]],
                 dss=[DS({0: 2}), DS({DUP: 2}), DS({})], hdim=DUP)
    x = g.placeholder("X", (12, 16, 32), [x_annot])
    w = g.parameter("W", (32, 64), [w_dup])
    w2 = g.comm(w, w_tp)
    y = g.dot(g.gelu(x), w2, name="Y")
    y_next = HSPMD(dgs=[[0, 3], [5, 6], [1]],
                   dss=[DS({0: 2}), DS({1: 2}), DS({})], hdim=0)
    g.comm(y, y_next, name="Y2")
    g.deduce()

    plan = resolve_comm_ops(g)[1].plan
    for dev in range(7):
        mine = [i.kind for i in device_items(plan, dev, "comm2")]
        via_specialize = [i.kind for i in specialize(g, dev).items
                          if i.role == "comm" and i.name == "comm2"]
        assert mine == via_specialize, (dev, mine, via_specialize)


def test_build_switch_step_sim_backend():
    """train.steps.build_switch_step wires the dynamic-switch migration
    (simulator backend runs in-process; the jax backend is covered by the
    subprocess selftest)."""
    from repro.core.graph import Graph
    from repro.core.simulator import gather, scatter
    from repro.train.steps import build_switch_step

    g = Graph()
    g.parameter("W", (16, 8), [spmd([0, 1], DS({0: 2})),
                               spmd([2, 3], DS({1: 2}))])
    g.deduce()
    rng = np.random.default_rng(0)
    value = rng.normal(size=(16, 8)).astype(np.float32)
    weights = {"W": scatter(value, g.tensors["W"].annots[0])}
    step = build_switch_step(g, 0, 1)
    out = step(weights)
    np.testing.assert_allclose(gather(out["W"]), value, atol=1e-6)


def test_fusion_round_schedule_is_valid_and_complete():
    """Static check of the batched-permute schedule: every point-to-point
    delivery lands in exactly one round, and no round reuses a source or
    a destination (ppermute's partial-permutation contract)."""
    from repro.core.comm_resolve import resolve
    from repro.runtime.lowering import DeviceOrder, PlanLowering

    src = spmd([0, 1, 2, 3], DS({0: 4}))
    dst = spmd([0, 1, 2, 3], DS({DUP: 4}))  # AG: all-to-all multicast
    plan = resolve(src, dst, (16, 8))
    lowering = PlanLowering(plan, (16, 8), DeviceOrder.for_plan(plan),
                            "dev", 4)
    pairs = set()
    for rounds in lowering._stage_rounds:
        for r in rounds:
            srcs = [s for s, _, _ in r.pairs]
            dsts = [d for _, d, _ in r.pairs]
            assert len(set(srcs)) == len(srcs), srcs
            assert len(set(dsts)) == len(dsts), dsts
            for s, d, g in r.pairs:
                assert (s, d, id(g)) not in pairs
                pairs.add((s, d, id(g)))
    assert len(pairs) == 12  # 4 x 3 multicast
    assert sum(len(r) for r in lowering._stage_rounds) == 3  # in-degree
    # the full-mesh AG itself lowers on the uniform gather path, so the
    # stats report ZERO emitted pairs/permutes — the fused schedule is
    # the fallback (see selftest fusion:stats for the narrow-plan case)
    assert lowering.stats.uniform_copy_stages == 1
    assert lowering.stats.copy_pairs == lowering.stats.ppermute_calls == 0


def test_scatter_integer_decompose_partials_sum_exactly():
    """The differential layer's integer decomposition: partial summands
    are integers and reassemble without rounding."""
    from repro.core.simulator import gather, scatter
    from repro.runtime import integer_decompose

    value = np.arange(32.0, dtype=np.float32).reshape(8, 4)
    annot = spmd([0, 1, 2, 3], DS({PARTIAL: 4}))
    st = scatter(value, annot, decompose=integer_decompose)
    for arr in st.parts.values():
        np.testing.assert_array_equal(arr, np.round(arr))
    np.testing.assert_array_equal(gather(st), value)
