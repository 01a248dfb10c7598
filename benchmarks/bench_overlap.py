"""Async MPMD executor overlap benchmark + permute-fusion micro-bench.

Two halves, matching the PR's two perf claims:

* **pipeline overlap** (``cases``): fwd+bwd training steps/s of the
  transformer bench configs under the async MPMD executor
  (``repro.runtime.async_program``) vs its own ``serialize=True``
  baseline — the SAME per-stage programs and channels, but blocking
  after every issue — and vs the scanned single-program ``JaxExecutor``.
  The measured overlap fraction is ``1 - t_async / t_serialized``: the
  share of wall time the double-buffered channels and eager grad-reduce
  actually hid.  Losses are asserted bit-equal across all three, so the
  numbers compare identical computations.  On forced host-CPU devices
  at toy sizes the scanned program usually stays ahead of per-stage
  dispatch (XLA fuses across the whole step; python dispatch is the
  async bottleneck, recorded as ``dispatch_bound``) — the JSON records
  whatever is true.

* **permute fusion** (``micro``): batched-permute rounds
  (``PlanLowering`` default) vs GSPMD-style per-pair resharding
  (``lower_plan(..., fuse_permutes=False)`` — one ppermute per
  (src, dst) pair, uniform fast paths off) on resharding-heavy plans.
  Outputs are asserted bitwise equal; the JSON records collective
  launches and µs per call for both lowerings.

::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.bench_overlap [--smoke]

``--smoke`` (what CI runs) keeps one pipeline config and single-shot
timings, asserts bit-equality plus the structural invariants (fused
launches < unfused pairs; per-stage program count), and leaves
``BENCH_overlap.json`` untouched; the default run rewrites the JSON.
"""

from __future__ import annotations

import argparse
import json
import time

# (config, parallelism, num_microbatches): the pipelined llama case is
# the one overlap can help; qwen dp2tp2 is the no-pipeline control
# (m=1: its qkv-bias add breaks microbatch role propagation for m>1,
# same restriction as bench_graph_block)
CASES = [
    ("qwen2_1_5b", dict(dp=2, tp=2, pp=1), 1),
    ("llama_32b", dict(dp=1, tp=2, pp=2), 2),
]
B, S = 2, 8
MICRO_SHAPE = (256, 256)


def _time_calls(fn, warmup, iters):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def _micro_plans(n: int):
    """Resharding-heavy (src, dst) pairs over ``n`` devices: a pure
    ring permutation (n pairs -> 1 fused round) and the row->column
    reshard (n*(n-1) pairs -> n-1 fused rounds)."""
    from repro.core.annotations import DS, spmd

    devs = list(range(n))
    return {
        "permute": (spmd(devs, DS({0: n})),
                    spmd(devs[1:] + devs[:1], DS({0: n}))),
        "reshard": (spmd(devs, DS({0: n})), spmd(devs, DS({1: n}))),
    }


def micro(n: int, warmup: int, iters: int) -> dict:
    import jax
    import numpy as np

    from repro.core.comm_resolve import resolve
    from repro.core.simulator import scatter
    from repro.launch.mesh import make_runtime_mesh
    from repro.runtime.lowering import (DeviceOrder, LoweringStats,
                                        lower_plan, pack_shards)

    mesh = make_runtime_mesh(n)
    rng = np.random.default_rng(0)
    value = rng.standard_normal(MICRO_SHAPE).astype(np.float32)
    out: dict = {}
    for name, (src, dst) in _micro_plans(n).items():
        plan = resolve(src, dst, MICRO_SHAPE)
        order = DeviceOrder.for_plan(plan)
        st = scatter(value, src, rng=np.random.default_rng(5))
        packed = pack_shards(st.parts, plan.src, MICRO_SHAPE,
                             int(mesh.devices.size), order)
        entry: dict = {"kind": plan.kind}
        outs = {}
        for mode, fuse in (("fused", True), ("gspmd_per_pair", False)):
            stats = LoweringStats()
            fn = lower_plan(plan, MICRO_SHAPE, mesh, order,
                            stats_out=stats, fuse_permutes=fuse)
            call = lambda fn=fn: jax.block_until_ready(fn(packed))
            outs[mode] = np.asarray(call())
            entry[mode] = {
                "seconds_per_call": _time_calls(call, warmup, iters),
                "copy_pairs": stats.copy_pairs,
                "ppermute_calls": stats.ppermute_calls,
                "uniform_copy_stages": stats.uniform_copy_stages,
            }
        np.testing.assert_array_equal(
            outs["fused"], outs["gspmd_per_pair"],
            err_msg=f"{name}: fused and per-pair lowerings diverged")
        assert entry["fused"]["ppermute_calls"] <= \
            entry["gspmd_per_pair"]["ppermute_calls"], entry
        entry["launch_ratio"] = (
            entry["gspmd_per_pair"]["ppermute_calls"]
            / max(entry["fused"]["ppermute_calls"], 1))
        entry["speedup"] = (
            entry["gspmd_per_pair"]["seconds_per_call"]
            / entry["fused"]["seconds_per_call"])
        out[name] = entry
    return out


def bench(smoke: bool = False) -> dict:
    import jax
    import numpy as np

    from repro import api
    from repro.configs import get_config
    from repro.models.graph_block import block_program, init_block_weights

    warmup, iters = (0, 1) if smoke else (1, 3)
    cases = [c for c in CASES if c[2] > 1] if smoke else CASES
    n_host = len(jax.devices())
    out: dict = {"batch": B, "seq": S, "smoke": smoke, "cases": {},
                 "devices_available": n_host}

    for arch, par, m in cases:
        n_dev = par["dp"] * par["tp"] * par["pp"]
        if n_host < n_dev:
            continue
        cfg = get_config(arch).reduced()
        prog = block_program(cfg, batch=B, seq=S, **par)
        rng = np.random.default_rng(0)
        ws = init_block_weights(prog, rng)
        feeds = {
            "ids": rng.integers(0, cfg.vocab, (B, S)).astype(np.int32),
            "labels": rng.integers(0, cfg.vocab,
                                   (B, S)).astype(np.int32)}
        label = f"{arch}/dp{par['dp']}tp{par['tp']}pp{par['pp']}/m{m}"
        case: dict = {"devices": n_dev, "num_microbatches": m}

        losses = {}
        for exn, ex in (("jax", api.JaxExecutor()),
                        ("async", api.AsyncExecutor()),
                        ("async_serialized",
                         api.AsyncExecutor(serialize=True))):
            sess = api.Session(prog, 0, executor=ex)
            sess.load(dict(ws))
            losses[exn] = sess.train_step(dict(feeds),
                                          num_microbatches=m).loss
            sess = api.Session(prog, 0, executor=ex)
            sess.load(dict(ws))
            sec = _time_calls(
                lambda s=sess: s.train_step(dict(feeds),
                                            num_microbatches=m),
                warmup, iters)
            case[exn] = {"seconds_per_step": sec,
                         "steps_per_second": 1.0 / sec,
                         "loss_step0": losses[exn]}
        assert losses["async"] == losses["jax"] == \
            losses["async_serialized"], losses

        t_async = case["async"]["seconds_per_step"]
        t_serial = case["async_serialized"]["seconds_per_step"]
        case["overlap_fraction"] = 1.0 - t_async / t_serial
        case["async_vs_jax"] = (case["jax"]["seconds_per_step"]
                                / t_async)
        # honest bottleneck label: per-stage python dispatch vs the
        # single fused scan
        case["dispatch_bound"] = case["async_vs_jax"] < 1.0

        ax = api.AsyncExecutor()
        lw = ax.lowered(prog.compile_train(0, loss="loss"))
        case["programs"] = len(lw.programs)
        case["channels"] = len(lw.channels)
        case["channel_kinds"] = sorted(ch.kind for ch in lw.channels)
        if smoke:
            # structural gates: per-(virtual stage, phase) programs and
            # hoisted comm channels really exist on the pipelined case
            assert case["programs"] == 2 * par["pp"], case
            assert "p2p" in case["channel_kinds"], case
        out["cases"][label] = case

    out["micro"] = micro(min(n_host, 4), warmup, max(iters, 1) * 4)
    return out


def rows(report: dict | None = None):
    report = report or bench()
    out = []
    for label, case in sorted(report["cases"].items()):
        for exn in ("jax", "async", "async_serialized"):
            sec = case[exn]["seconds_per_step"]
            out.append((f"overlap/{label}/{exn}", sec,
                        f"steps_per_s={1.0 / sec:.2f} "
                        f"loss0={case[exn]['loss_step0']:.6g}"))
        out.append((f"overlap/{label}/summary", 0.0,
                    f"overlap_fraction={case['overlap_fraction']:.3f} "
                    f"async_vs_jax={case['async_vs_jax']:.2f}x "
                    f"programs={case['programs']} "
                    f"channels={case['channels']}"))
    for name, entry in sorted(report.get("micro", {}).items()):
        out.append((
            f"overlap/micro/{name}/fused",
            entry["fused"]["seconds_per_call"],
            f"launches={entry['fused']['ppermute_calls']}"))
        out.append((
            f"overlap/micro/{name}/gspmd_per_pair",
            entry["gspmd_per_pair"]["seconds_per_call"],
            f"launches={entry['gspmd_per_pair']['ppermute_calls']} "
            f"launch_ratio={entry['launch_ratio']:.1f}x "
            f"speedup={entry['speedup']:.2f}x"))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one config, single-shot timings (CI liveness)")
    args = ap.parse_args()
    report = bench(smoke=args.smoke)
    for name, seconds, derived in rows(report):
        print(f"{name},{seconds * 1e6:.0f},{derived}")
    if args.smoke:
        print("smoke ok (BENCH_overlap.json left untouched)")
        return
    with open("BENCH_overlap.json", "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    print("wrote BENCH_overlap.json")


if __name__ == "__main__":
    main()
