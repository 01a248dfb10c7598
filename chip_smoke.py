"""Train qwen2-1.5b through the HSPMD path on a TPU, and check it.

    python chip_smoke.py               # one chip: 3 training steps
    python chip_smoke.py --four-chip   # dp2tp2 on four chips vs dp1tp1

The model is qwen2-1.5b at its published widths, built as a graph-IR
block program (``models.graph_block.block_program``) and trained through
the normal entry points: ``Program.compile_train`` -> ``Session.
train_step`` on ``api.JaxExecutor``.  Depth is cut (``LAYERS`` of 28) so
that fp32 weights, gradients and activations fit one 16 GB chip; the
cut is printed.  Weights and tokens are random, drawn from ``SEED``.

One chip (the default): the step program is compiled ahead of time to
read its size (``memory_analysis``) and to check that the Pallas flash
kernel is in it, then 3 steps run.  The steps must compile nothing
more, so the executable checked is the one that ran.  Step 0's loss
and gradients are compared with the plain float32 ``models.layers``
twin of the block (``graph_block.reference_loss``).  Both run with
float32 matmuls
(``jax.default_matmul_precision("highest")``).  The same reference at
the chip's default precision (one bfloat16 pass per matmul) must then
miss the tolerance: that shows the comparison can tell the two apart.

``--four-chip`` runs only the layout this system exists for: the same
model, batch and weights under dp=2, tp=2 on four chips, against the
dp1tp1 program on one chip of the same host, after one step each.

Every phase that fails ends the run with a non-zero exit code and no
result line.  The script needs a TPU: on any other platform it names
what it found and exits with code 2.  The wall times it prints are
smoke timings of single steps, not measurements.  It starts no other
process.  The last line of a passing run is one JSON object naming the
device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen2-1.5b"
#: published widths (Qwen2 technical report, arXiv:2407.10671, Table 1)
PUBLISHED = {"d_model": 1536, "n_heads": 12, "n_kv_heads": 2, "hd": 128,
             "d_ff": 8960, "vocab": 151936, "qkv_bias": True,
             "tie_embeddings": True}
#: the cut: 4 of 28 layers, 4 sequences of 1024 tokens.  The embedding
#: alone is 233 M parameters; with 4 layers the model is 420 M (1.68 GB
#: fp32), and weights + gradients + step activations must fit a 16 GB
#: chip with room left (MEMORY_SHARE).  Batch 4 divides over dp=2.
LAYERS, BATCH, SEQ = 4, 4, 1024
SEED = 0
STEPS = 3
#: share of the chip's memory one compiled step may take
MEMORY_SHARE = 0.9
#: Tolerance on step 0 against the float32 reference: the relative
#: error of the loss, and per gradient the RMS of the difference over
#: the larger of the reference gradient's RMS and the RMS over all
#: gradients.  That floor is there because the key-bias gradient is
#: zero in exact arithmetic (softmax does not see a shift along the key
#: axis), so what the two sides compute for it is rounding noise.
#: float32 matmuls over 8960-long sums keep the relative error near
#: 1e-5; a single bfloat16 pass rounds each operand to 8 bits
#: (relative 2^-9 ~ 2e-3 per element), which no gradient survives
#: below 2e-4.
TOL = 2e-4


class SmokeFailure(Exception):
    """A phase of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def published_config():
    from repro.configs import get_config

    cfg = get_config(ARCH)
    got = {k: getattr(cfg, k) for k in PUBLISHED}
    check(got == PUBLISHED, f"{ARCH} config {got} is not the published "
                            f"{PUBLISHED}")
    return cfg


def leaf_dtypes(lw) -> dict:
    """Token ids and labels are int32 feeds; every parameter is fp32."""
    return {t.name: np.int32 if t.name in ("ids", "labels") else np.float32
            for t in lw.leaves}


def check_kernel_compiled(text: str) -> None:
    check("tpu_custom_call" in text,
          "the compiled step holds no Pallas kernel (tpu_custom_call)")


class StepWatch:
    """What the train steps ran: the seconds of each call of the step
    program (from the call to its outputs being ready, so waiting for
    the inputs' transfer is in it), the devices holding its outputs,
    and every executable JAX compiled or read from its persistent cache
    meanwhile (each records ``COMPILE_EVENT``)."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, lw):
        self.calls, self.devices, self.compiled = [], set(), []
        self._lw, self._run = lw, lw.fn
        lw.fn = self._call

    def _call(self, *args):
        import jax

        t0 = time.perf_counter()
        outs = jax.block_until_ready(self._run(*args))
        self.calls.append(time.perf_counter() - t0)
        self.devices.update(sh.device for o in outs
                            for sh in o.addressable_shards)
        return outs

    def _event(self, event, secs, fun_name="?", **_):
        if event == self.COMPILE_EVENT:
            self.compiled.append(fun_name)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)
        self._lw.fn = self._run


def compile_step(ex, tplan, label: str, limit: int):
    """Compile the train step ahead of time, print its size and
    attention tallies, and check that it fits and that attention runs
    on the Pallas kernel.  ``train_step`` then runs this executable
    (checked by ``train_steps``)."""
    lw = ex.lowered(tplan, tplan.train_fetches)
    t0 = time.perf_counter()
    compiled = lw.lower(leaf_dtypes(lw)).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    st = lw.stats
    print(f"[{label}] compile {seconds:.1f} s on "
          f"{lw.mesh.devices.size} device(s); per device: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / 1e9:.2f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, total "
          f"{total / 1e9:.2f} GB of {limit / 1e9:.2f} GB")
    print(f"[{label}] attention dispatches: pallas {st.pallas_dispatches}"
          f", ref {st.ref_dispatches}; segments {st.compute_segments}, "
          f"straight-line {st.straightline_segments}, grouped reduces "
          f"{st.grouped_reduces}")
    check(total <= MEMORY_SHARE * limit,
          f"[{label}] step needs {total / 1e9:.2f} GB, over "
          f"{MEMORY_SHARE:.0%} of the chip's {limit / 1e9:.2f} GB")
    check(st.pallas_dispatches > 0 and st.ref_dispatches == 0,
          f"[{label}] attention did not all lower onto the Pallas kernel "
          f"(pallas {st.pallas_dispatches}, ref {st.ref_dispatches})")
    check_kernel_compiled(compiled.as_text())
    return lw


def train_steps(sess, lw, devices, feeds, steps: int, label: str):
    """Run ``steps`` train steps of the compiled step ``lw``; check that
    they compile nothing and that their outputs are held on every one
    of ``devices``.  Returns step 0's TrainResult."""
    first = None
    with StepWatch(lw) as watch:
        for i in range(steps):
            t0 = time.perf_counter()
            r = sess.train_step(dict(feeds))
            dt = time.perf_counter() - t0
            print(f"[{label}] step {i}: loss {r.loss:.9g}, grad norm "
                  f"{r.metrics['grad_norm']:.6g} (smoke timing, one "
                  f"step: {dt:.2f} s wall, of which the step program's "
                  f"call {watch.calls[-1]:.2f} s)")
            check(np.isfinite(r.loss)
                  and np.isfinite(r.metrics["grad_norm"]),
                  f"[{label}] step {i} is not finite")
            if first is None:
                first = r
    check(len(watch.calls) == steps,
          f"[{label}] {steps} steps made {len(watch.calls)} calls of the "
          f"compiled step")
    check(not watch.compiled,
          f"[{label}] the steps compiled {watch.compiled}: the executable "
          f"checked is not the one that ran")
    check(watch.devices == set(devices),
          f"[{label}] step outputs on {sorted(map(str, watch.devices))}, "
          f"not on all of {sorted(map(str, devices))}")
    print(f"[{label}] the steps compiled nothing; outputs held on "
          f"{len(watch.devices)} chip(s)")
    return first


def errors(loss, grads: dict, ref_loss, ref_grads: dict) -> dict:
    """Relative error of the loss and of each gradient (see TOL)."""
    ref = {n: np.asarray(g, np.float64) for n, g in ref_grads.items()}
    total = sum(float(np.sum(g * g)) for g in ref.values())
    rms_all = np.sqrt(total / sum(g.size for g in ref.values()))
    out = {"loss": abs(float(loss) - float(ref_loss))
           / abs(float(ref_loss))}
    for n, g in ref.items():
        diff = np.asarray(grads[n], np.float64) - g
        rms = np.sqrt(np.mean(g * g))
        out[n] = float(np.sqrt(np.mean(diff * diff)) / max(rms, rms_all))
    return out


def report(label: str, errs: dict) -> float:
    worst = max(errs, key=errs.get)
    print(f"[{label}] loss error {errs['loss']:.3e}; worst gradient "
          f"error {max(v for k, v in errs.items() if k != 'loss'):.3e} "
          f"({worst} is the worst of all: {errs[worst]:.3e})")
    return errs[worst]


def reference(cfg, ws, ids, labels, precision: str):
    """The float32 ``models.layers`` twin: loss and gradients on the
    default device, with the XLA attention (the reference must not run
    through the kernel it checks)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import policy
    from repro.models.graph_block import reference_loss

    before = policy.get_policy()
    policy.set_policy("ref")
    try:
        fn = jax.jit(jax.value_and_grad(
            reference_loss(cfg, LAYERS, ids, labels)))
        with jax.default_matmul_precision(precision):
            loss, grads = fn({n: jnp.asarray(w) for n, w in ws.items()})
            loss = float(loss)
            grads = {n: np.asarray(g) for n, g in grads.items()}
    finally:
        policy.set_policy(before)
    return loss, grads


def setup(cfg, dp: int, tp: int):
    from repro.models.graph_block import block_program, init_block_weights

    prog = block_program(cfg, batch=BATCH, seq=SEQ, n_layers=LAYERS,
                         dp=dp, tp=tp, pp=1)
    rng = np.random.default_rng(SEED)
    ws = init_block_weights(prog, rng)
    feeds = {"ids": rng.integers(0, cfg.vocab, (BATCH, SEQ), np.int32),
             "labels": rng.integers(0, cfg.vocab, (BATCH, SEQ), np.int32)}
    return prog, ws, feeds


def session(prog, ws, devices):
    from jax.sharding import Mesh

    from repro import api

    ex = api.JaxExecutor(mesh=Mesh(np.array(devices), ("dev",)))
    sess = api.Session(prog, 0, executor=ex)
    sess.load(ws)
    return ex, sess, prog.compile_train(0)


def memory_limit(dev) -> int:
    stats = dev.memory_stats() or {}
    check("bytes_limit" in stats,
          f"{dev.device_kind} reports no memory limit")
    return int(stats["bytes_limit"])


def one_chip(cfg, devices) -> None:
    import jax

    prog, ws, feeds = setup(cfg, 1, 1)
    n_params = sum(w.size for w in ws.values())
    print(f"[1 chip] {ARCH}: {n_params / 1e6:.1f} M parameters "
          f"(cut: {LAYERS} of {cfg.n_layers} layers; batch {BATCH} x "
          f"{SEQ} tokens)")
    with jax.default_matmul_precision("highest"):
        ex, sess, tplan = session(prog, ws, devices[:1])
        lw = compile_step(ex, tplan, "1 chip", memory_limit(devices[0]))
        first = train_steps(sess, lw, devices[:1], feeds, STEPS, "1 chip")
    grads = {n: first.grad_value(n) for n in ws}
    ref_loss, ref_grads = reference(cfg, ws, feeds["ids"],
                                    feeds["labels"], "highest")
    print(f"[reference] fp32 loss {ref_loss:.9g}")
    worst = report("1 chip vs fp32 reference",
                   errors(first.loss, grads, ref_loss, ref_grads))
    check(worst <= TOL, f"step 0 is {worst:.3e} from the fp32 reference "
                        f"(tolerance {TOL:g})")
    bf_loss, bf_grads = reference(cfg, ws, feeds["ids"], feeds["labels"],
                                  "default")
    sens = report("bf16-pass reference vs fp32 reference",
                  errors(bf_loss, bf_grads, ref_loss, ref_grads))
    check(sens > TOL, f"one-pass bfloat16 matmuls come within {sens:.3e} "
                      f"of the fp32 reference: tolerance {TOL:g} cannot "
                      f"tell them apart")


def four_chip(cfg, devices) -> None:
    import jax

    check(len(devices) >= 4, f"--four-chip needs 4 chips, found "
                             f"{len(devices)}")
    limit = memory_limit(devices[0])
    results = {}
    with jax.default_matmul_precision("highest"):
        for dp, tp, devs in ((2, 2, devices[:4]), (1, 1, devices[:1])):
            label = f"dp{dp}tp{tp}"
            prog, ws, feeds = setup(cfg, dp, tp)
            ex, sess, tplan = session(prog, ws, devs)
            lw = compile_step(ex, tplan, label, limit)
            first = train_steps(sess, lw, devs, feeds, 1, label)
            results[label] = (first.loss,
                              {n: first.grad_value(n) for n in ws})
            del sess, ex, lw, first
    loss, grads = results["dp2tp2"]
    ref_loss, ref_grads = results["dp1tp1"]
    worst = report("dp2tp2 on 4 chips vs dp1tp1 on 1 chip",
                   errors(loss, grads, ref_loss, ref_grads))
    check(worst <= TOL, f"dp2tp2 step is {worst:.3e} from dp1tp1 "
                        f"(tolerance {TOL:g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run dp2tp2 on four chips against dp1tp1 on "
                         "one, and nothing else")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    from repro.runtime.harness import use_compile_cache

    print(f"device: {dev.device_kind} ({dev.platform}), "
          f"{len(devices)} chip(s); compile cache {use_compile_cache()}")
    try:
        cfg = published_config()
        if args.four_chip:
            four_chip(cfg, devices)
        else:
            one_chip(cfg, devices)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
