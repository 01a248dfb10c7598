"""Graph-IR transformer block vs the plain-jax layers stack.

Seeds the perf trajectory for the graph-IR block (the PR 6 tentpole):
fwd+bwd training steps/s of ``models.graph_block.block_program`` on the
numpy simulator and — when enough host devices are forced — the jax
shard_map backend, against the unsharded plain-jax ``models.layers``
reference (jit'd ``jax.value_and_grad``), per reduced config.  The
ref-vs-pallas attention dispatch tallies of the lowered plan ride along
(``LoweringStats``; see docs/kernels.md), so the JSON records what the
compute seam actually dispatched — as do the specialization-class
emission counts (``switch_branches_emitted`` etc.; docs/lowering.md)
and the graph-jax/plain-jax steps/s ratio, so the structural claim
(homogeneous strategies lower switch-free) stays measured.  ``--smoke``
asserts the homogeneous dp2tp2 case really is at the straight-line
minimum: zero switch branches, every segment straight-line.  Emits
``BENCH_graph_block.json``::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.bench_graph_block [--smoke]

``--smoke`` (what CI runs) keeps one config and single-shot timings —
a liveness check for the whole graph-IR train path, not a measurement.
"""

from __future__ import annotations

import argparse
import json
import time

CASES = [
    # (config, parallelism): GQA + qkv bias + tied head, then an
    # untied 2-stage pipeline
    ("qwen2_1_5b", dict(dp=2, tp=2, pp=1)),
    ("llama_32b", dict(dp=1, tp=2, pp=2)),
]
B, S = 2, 8


def _reference_step(cfg, ids, labels):
    """jit'd fwd+bwd of the plain-jax twin of ``build_block``."""
    import jax

    from repro.models.graph_block import reference_loss

    return jax.jit(jax.value_and_grad(
        reference_loss(cfg, cfg.n_layers, ids, labels)))


def _time_calls(fn, warmup, iters):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return iters / (time.perf_counter() - t0)


def _dispatch_stats(prog, tplan):
    """Static ref/pallas dispatch tallies of the lowered train plan
    under each forced policy (no execution needed — the seam decides
    eagerly at lowering time)."""
    from repro import api
    from repro.kernels import policy

    out = {}
    for pol in ("ref", "pallas"):
        policy.set_policy(pol)
        try:
            lw = api.JaxExecutor().lowered(tplan, None)
            out[pol] = {"ref": lw.stats.ref_dispatches,
                        "pallas": lw.stats.pallas_dispatches}
        finally:
            policy.set_policy("auto")
    return out


def bench(smoke: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.configs import get_config
    from repro.models.graph_block import block_program, init_block_weights

    warmup, iters = (0, 1) if smoke else (1, 3)
    cases = CASES[:1] if smoke else CASES
    out: dict = {"batch": B, "seq": S, "smoke": smoke, "cases": {}}
    for arch, par in cases:
        cfg = get_config(arch).reduced()
        n_dev = par["dp"] * par["tp"] * par["pp"]
        prog = block_program(cfg, batch=B, seq=S, **par)
        rng = np.random.default_rng(0)
        ws = init_block_weights(prog, rng)
        ids = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
        labels = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
        feeds = {"ids": ids, "labels": labels}
        label = f"{arch}/dp{par['dp']}tp{par['tp']}pp{par['pp']}"
        case: dict = {"devices": n_dev}

        executors = {"sim": api.SimulatorExecutor()}
        if len(jax.devices()) >= n_dev:
            executors["jax"] = api.JaxExecutor()
        for exn, ex in executors.items():
            sess = api.Session(prog, 0, executor=ex)
            sess.load(dict(ws))
            loss0 = sess.train_step(dict(feeds), num_microbatches=1).loss
            sess = api.Session(prog, 0, executor=ex)
            sess.load(dict(ws))
            sps = _time_calls(
                lambda s=sess: s.train_step(dict(feeds),
                                            num_microbatches=1),
                warmup, iters)
            case[f"graph_{exn}"] = {"steps_per_second": sps,
                                    "loss_step0": loss0}

        step = _reference_step(cfg, ids, labels)
        jp = {n: jnp.asarray(v) for n, v in ws.items()}
        want, _ = step(jp)
        case["plain_jax"] = {
            "steps_per_second": _time_calls(
                lambda: jax.block_until_ready(step(jp)),
                max(warmup, 1), iters),
            "loss_step0": float(want),
        }
        if "graph_jax" in case:
            case["graph_jax"]["vs_plain_jax"] = (
                case["graph_jax"]["steps_per_second"]
                / case["plain_jax"]["steps_per_second"])
        if "jax" in executors:
            tplan = prog.compile_train(0, loss="loss")
            case["dispatches"] = _dispatch_stats(prog, tplan)
            lw = api.JaxExecutor().lowered(tplan, None)
            case["lowering"] = {
                "compute_segments": lw.stats.compute_segments,
                "straightline_segments": lw.stats.straightline_segments,
                "switch_branches_emitted":
                    lw.stats.switch_branches_emitted,
            }
            homogeneous = par["pp"] == 1
            if smoke and homogeneous:
                # the CI liveness gate for the specialization-class
                # lowering: a homogeneous (single-class) strategy must
                # emit NO switches at all — every segment straight-line
                assert case["lowering"]["switch_branches_emitted"] == 0, \
                    case["lowering"]
                assert case["lowering"]["straightline_segments"] == \
                    case["lowering"]["compute_segments"] > 0, \
                    case["lowering"]
        out["cases"][label] = case
    return out


def rows(report: dict | None = None):
    report = report or bench()
    out = []
    for label, case in sorted(report["cases"].items()):
        for kind in ("graph_sim", "graph_jax", "plain_jax"):
            if kind not in case:
                continue
            sps = case[kind]["steps_per_second"]
            out.append((f"graph_block/{label}/{kind}", 1.0 / sps,
                        f"steps_per_s={sps:.2f} "
                        f"loss0={case[kind]['loss_step0']:.6g}"))
        disp = case.get("dispatches")
        if disp:
            out.append((f"graph_block/{label}/dispatch", 0.0,
                        f"ref_policy={disp['ref']['ref']}ref+"
                        f"{disp['ref']['pallas']}pallas "
                        f"pallas_policy={disp['pallas']['ref']}ref+"
                        f"{disp['pallas']['pallas']}pallas"))
        low = case.get("lowering")
        if low:
            out.append((f"graph_block/{label}/lowering", 0.0,
                        f"segments={low['compute_segments']} "
                        f"straightline={low['straightline_segments']} "
                        f"switch_branches="
                        f"{low['switch_branches_emitted']}"))
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
        print("smoke ok (BENCH_graph_block.json left untouched)")
        return
    with open("BENCH_graph_block.json", "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    print("wrote BENCH_graph_block.json")


if __name__ == "__main__":
    main()
