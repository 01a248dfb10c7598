"""On-chip training benchmark of the HSPMD system: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration trained under a traffic mix on 1 or 4 chips.  Everything
else is found by name, so a later change adds a cell, a configuration,
a traffic mix or a metric as new files plus entries in
``BENCHMARK.json``, and edits none:

- ``bench/configs/<config>.json``: the model's sizes as run, its source,
  the keys cut from it (``reduced``), what was ``assumed``, the precision,
  and its architecture (``arch``);
- ``bench/archs/<arch>.py``: what depends on the architecture: the
  program as the system builds it, the parameters in the program's
  order, the plain float32 loss, the training work per token, the
  flash-attention calls of a step and the CPU-size twin (see
  ``bench/archs/dense.py``);
- ``bench/traffic/<traffic>.json``: the job (batch, sequence length,
  optimizer settings); ids and labels are drawn from ``(seed, step)``;
- ``bench/workloads/<cell>.json``: the layout (``dp``, ``tp``) and the
  limit of each number that decides ``correct`` (see ``bench/check.py``);
- ``bench/metrics/<metric>.py``: ``read(run) -> float | None`` for one
  metric (``run`` is a :class:`Run`); ``None`` leaves it out of the line.

The run drives the system as a user would: the architecture's
``program`` -> ``Program.compile_train`` -> ``Session.train_step`` on
``api.JaxExecutor`` over a mesh of the cell's chips.  Set-up makes the
weights from the seed on the device in one jitted call and loads them,
compiles the step ahead of time, and runs the first three steps, which
the plain reference (``bench/reference.py``) then follows.  The window
is closed-loop steps for ``--seconds``; a step started before the end
is finished and counted, and no compile may happen in it.  After the
window the program's state is freed and the reference runs; its
comparison decides ``correct``.  ``--trace 1`` traces the window with
the JAX profiler and reports the per-layer metrics instead of the
end-to-end ones.

It needs a TPU with at least the cell's chips: elsewhere it exits with
code 2 and prints no result.  The last line of standard output is the
result; the last lines of standard error are the numbers compared, each
beside its limit.  JAX's compile cache is ``JAX_COMPILATION_CACHE_DIR``
where that is set, else ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: steps in set-up; the reference follows exactly these
FIRST_STEPS = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """The run cannot give a result."""


class NoChip(BenchError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a metric reader sees.  Times are host-clock seconds."""

    cell: dict
    model: dict
    traffic: dict
    chips: int
    peaks: dict | None
    setup_s: float = 0.0
    setup_spans: dict = field(default_factory=dict)
    steps: int = 0
    tokens: int = 0
    window_s: float = 0.0
    step_spans: dict = field(default_factory=dict)
    trace: dict | None = None
    memory_peak_bytes: int | None = None

    @property
    def arch(self):
        return self.cell["arch"]

    @property
    def flops_per_step(self) -> float:
        return self.arch.train_flops_per_token(
            self.model, self.traffic["seq"]) \
            * self.traffic["batch"] * self.traffic["seq"]


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_file(path: str, prefix: str):
    """The Python file at ``path`` as a module named ``prefix`` plus
    its base name."""
    base = os.path.basename(path)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(
        prefix + base.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(name: str, root: str = ROOT):
    """The architecture module ``bench/archs/<name>.py`` under
    ``root``."""
    path = os.path.join(root, "bench", "archs", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no architecture module {path}")
    return load_file(path, "bench_arch_")


def load_cell(name: str, root: str = ROOT) -> dict:
    """The ``BENCHMARK.json`` entry of cell ``name`` with its files:
    ``model``, its architecture module ``arch``, ``traffic_mix``,
    ``layout``, ``limits`` and the metrics it reports (``end_to_end``,
    ``per_layer``: ``[(name, unit)]``)."""
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    bench = os.path.join(root, "bench")
    cell = dict(entry)
    cell["model"] = read_json(os.path.join(bench, "configs",
                                           entry["config"] + ".json"))
    cell["arch"] = load_arch(cell["model"]["arch"], root)
    cell["traffic_mix"] = read_json(os.path.join(bench, "traffic",
                                                 entry["traffic"] + ".json"))
    cell.update(read_json(os.path.join(bench, "workloads", name + ".json")))
    for kind in ("end_to_end", "per_layer"):
        cell[kind] = [(m["name"], m["unit"]) for m in spec[kind]
                      if name in m.get("workloads", [name])]
    return cell


def feeds(seed: int, step: int, batch: int, seq: int, vocab: int) -> dict:
    """Step ``step``'s token ids and labels, uniform over the
    vocabulary."""
    rng = np.random.default_rng([seed, step])
    return {"ids": rng.integers(0, vocab, (batch, seq), dtype=np.int32),
            "labels": rng.integers(0, vocab, (batch, seq), dtype=np.int32)}


def devices_for(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), found {len(devs)}")
    return devs[:chips]


def device_peaks(kind: str) -> dict:
    peaks = read_json(os.path.join(BENCH, "peaks.json"))
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return peaks[kind]


def compile_cache() -> str:
    """Turn the program's persistent compile cache on, at its fixed
    path, and keep every executable in it, also the small ones that
    make the weights and run the reference."""
    import jax
    from repro.runtime.harness import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devs) -> int | None:
    """Peak device bytes on the fullest device: the peak of buffers in
    use plus the peak reserved for executables' temporaries, which the
    TPU allocator keeps apart from the buffers."""
    stats = [d.memory_stats() or {} for d in devs]
    if not all("peak_bytes_in_use" in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
               for s in stats)


def host_memory() -> str:
    """This process's resident and peak resident host memory."""
    with open("/proc/self/status") as f:
        kv = dict(line.split(":", 1) for line in f if ":" in line)
    return " ".join(f"{k} {int(kv[k].split()[0]) / 1e6:.2f} GB"
                    for k in ("VmRSS", "VmHWM") if k in kv)


def cpu_ticks() -> tuple[int, int]:
    """The host's CPU time so far, in clock ticks: all of it, and the
    part the hypervisor gave to other machines (steal), which shows
    when the host's neighbours slow a run."""
    with open("/proc/stat") as f:
        ticks = [int(x) for x in f.readline().split()[1:9]]
    return sum(ticks), ticks[7] if len(ticks) > 7 else 0


class Spans:
    """Host seconds per named span, each also a ``bench.<name>``
    profiler annotation."""

    def __init__(self):
        self.totals: dict[str, float] = defaultdict(float)

    @contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.totals[name] += time.perf_counter() - t0

    def wrap(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped


@contextmanager
def instrumented(spans: Spans, lw):
    """Span the calls into each layer of a train step: the host
    optimizer, packing and placing the leaves, the step program's call
    up to its outputs being ready, and fetching them."""
    import jax
    import repro.optim.adamw as adamw

    optimizer, call = adamw.sharded_apply_updates, lw.fn

    def timed_call(*args):
        with spans.span("call"):
            return jax.block_until_ready(call(*args))

    adamw.sharded_apply_updates = spans.wrap("optimizer", optimizer)
    lw.fn = timed_call
    lw._pack = spans.wrap("feed", lw._pack)
    lw._put_all = spans.wrap("feed", lw._put_all)
    lw._fetch_rows = spans.wrap("fetch", lw._fetch_rows)
    try:
        yield
    finally:
        adamw.sharded_apply_updates = optimizer
        lw.fn = call
        for name in ("_pack", "_put_all", "_fetch_rows"):
            del lw.__dict__[name]


class GcWatch:
    """Pauses of Python's garbage collector while it is entered:
    ``{generation: [collections, seconds]}``."""

    def __init__(self):
        self.pauses = defaultdict(lambda: [0, 0.0])
        self._t0 = 0.0

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            entry = self.pauses[info["generation"]]
            entry[0] += 1
            entry[1] += time.perf_counter() - self._t0

    def __enter__(self):
        gc.callbacks.append(self._event)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._event)


class CompileWatch:
    """Names of the executables JAX compiles (or reads from its
    persistent cache) while it is entered."""

    def __init__(self):
        self.compiled: list[str] = []

    def _event(self, event, secs, fun_name="?", **_):
        if event == COMPILE_EVENT:
            self.compiled.append(fun_name)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)


def first_grad_norms(sess, b1: float) -> dict:
    """Per leaf, the norm of the first gradient as the optimizer got
    it, from its first moment after one step (``m = (1 - b1) g``)."""
    from repro.core.simulator import gather

    from bench import check

    scale = float(np.float32(1 - b1))
    return {name: check.norm(gather(st)) / scale
            for name, st in sess.opt_state["m"].items()}


def change_norms(sess, start: dict) -> dict:
    """Per leaf, the norm of the weights' change from ``start`` (device
    arrays)."""
    from bench import check

    return {name: check.norm(sess.weight_value(name), np.asarray(w0))
            for name, w0 in start.items()}


def log_window_noise(t0, marks, ticks0, use0, gcw, log) -> None:
    """What the host did besides the steps in the window: the spread of
    step times and the spans of the slowest step, the collector's
    pauses, page faults, context switches forced on the process, and
    CPU time stolen by the host's neighbours."""
    secs = np.diff([t0] + [t for t, _ in marks])
    k = int(np.argmax(secs))
    before = marks[k - 1][1] if k else {}
    spans = {n: round(v - before.get(n, 0.0), 3)
             for n, v in marks[k][1].items()}
    total, steal = np.subtract(cpu_ticks(), ticks0)
    use = resource.getrusage(resource.RUSAGE_SELF)
    log(f"window steps: {secs.min():.3f}-{secs.max():.3f} s, median "
        f"{np.median(secs):.3f}; slowest (step {k}) spans {spans}; GC "
        f"pauses {dict(gcw.pauses)}; page faults minor "
        f"{use.ru_minflt - use0.ru_minflt} major "
        f"{use.ru_majflt - use0.ru_majflt}; forced context switches "
        f"{use.ru_nivcsw - use0.ru_nivcsw}; CPU stolen "
        f"{100 * steal / max(total, 1):.2f}%")


def reference_record(arch, m: dict, opt: dict, seed: int, batch: int,
                     seq: int, precision: str = "highest",
                     log=lambda *_: None) -> dict:
    """The reference's record of the first ``FIRST_STEPS`` steps of
    model ``m`` of architecture ``arch``, on the default device at
    ``precision``.  Weights and gradients stay on the device; AdamW runs
    there leaf by leaf, with the moments kept on the host between steps
    (at 14B widths they would not fit beside the gradients on one
    chip)."""
    import jax
    import jax.numpy as jnp

    from bench import check, reference

    make = reference.weight_maker(arch, m)
    loss_and_grad = reference.loss_and_grad(arch, m)
    clip, update = reference.adamw(opt)
    lo, hi = reference.seed_words(seed)
    params = make(lo, hi)
    mom, vel = {}, {}
    rec: dict = {"loss": [], "grad": {}}
    times = []
    for t in range(FIRST_STEPS):
        t0 = time.perf_counter()
        f = feeds(seed, t, batch, seq, m["vocab_size"])
        with jax.default_matmul_precision(precision):
            loss, grads = loss_and_grad(params, f["ids"], f["labels"])
        rec["loss"].append(float(loss))
        scale = clip(grads)
        for name in list(params):
            zero = jnp.zeros_like(params[name])
            params[name], mo, ve, g = update(
                params[name], grads.pop(name), mom.pop(name, zero),
                vel.pop(name, zero), scale, np.int32(t + 1))
            if t == 0:
                rec["grad"][name] = check.norm(g)
            del g
            if t + 1 < FIRST_STEPS:
                mom[name], vel[name] = jax.device_get((mo, ve))
        times.append(time.perf_counter() - t0)
    start = make(lo, hi)
    rec["change"] = {name: check.norm(params.pop(name), start.pop(name))
                     for name in list(params)}
    log(f"reference steps {[round(x, 2) for x in times]} s")
    return rec


def leaf_dtypes(lw) -> dict:
    return {t.name: np.int32 if t.name in ("ids", "labels") else np.float32
            for t in lw.leaves}


def read_metrics(names, run: Run) -> dict:
    """``{name: {"value", "unit"}}`` from each metric's reader; a reader
    that finds nothing to read is left out."""
    out = {}
    for name, unit in names:
        mod = load_file(os.path.join(BENCH, "metrics", name + ".py"),
                        "bench_metric_")
        value = mod.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def trace_window(trace: bool):
    """Start the profiler for the window (a no-op without ``trace``);
    returns a function that stops it and gives the trace summary."""
    import jax

    from bench import trace as tracing

    if not trace:
        return lambda: None
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)

    def stop():
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                              recursive=True)
            return tracing.summarize(files[0]) if files else None
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    return stop


class Trainer:
    """The compiled train step with its session, as set-up builds it
    for one cell; :meth:`start` loads a seed's weights and runs the
    first steps, :meth:`step` runs one more, :meth:`release` drops the
    weights and optimizer state."""

    def __init__(self, cell: dict, devs: list, spans: "Spans", log=print):
        import jax
        from jax.sharding import Mesh
        from repro import api
        from repro.optim.adamw import AdamWConfig

        from bench import reference

        self.cell, self.spans = cell, spans
        self.m, self.tr = cell["model"], cell["traffic_mix"]
        self.precision = self.m["precision"]["matmul"]
        self.make = reference.weight_maker(cell["arch"], self.m)
        with jax.default_matmul_precision(self.precision):
            with spans.span("plan"):
                prog = cell["arch"].program(self.m, self.tr["batch"],
                                            self.tr["seq"], cell["layout"])
                ex = api.JaxExecutor(mesh=Mesh(np.array(devs), ("dev",)))
                self.sess = api.Session(
                    prog, 0, executor=ex,
                    optimizer=AdamWConfig(**self.tr["optimizer"]))
                tplan = prog.compile_train(0)
            with spans.span("compile"):
                self.lw = ex.lowered(tplan, tplan.train_fetches)
                compiled = self.lw.lower(leaf_dtypes(self.lw)).compile()
        mem = compiled.memory_analysis()
        st = self.lw.stats
        log(f"step program per device: arguments "
            f"{mem.argument_size_in_bytes / 1e9:.3f} GB, outputs "
            f"{mem.output_size_in_bytes / 1e9:.3f} GB, temp "
            f"{mem.temp_size_in_bytes / 1e9:.3f} GB; attention dispatches "
            f"pallas {st.pallas_dispatches}, ref {st.ref_dispatches}; "
            f"segments {st.compute_segments}, grouped reduces "
            f"{st.grouped_reduces}")

    def step(self, seed: int, i: int) -> float:
        """Train step ``i`` of ``seed``'s traffic; returns its loss."""
        import jax

        tr = self.tr
        with jax.default_matmul_precision(self.precision), \
                self.spans.span("step"):
            out = self.sess.train_step(feeds(seed, i, tr["batch"],
                                             tr["seq"],
                                             self.m["vocab_size"]))
        return out.loss

    def start(self, seed: int, log=print) -> dict:
        """Load ``seed``'s weights and run the first steps; returns the
        program's record of them (see ``bench/check.py``)."""
        import jax

        from bench import reference

        lo, hi = reference.seed_words(seed)
        weights = jax.device_get(self.make(lo, hi))
        with self.spans.span("plan"):
            self.sess.load(weights)
        del weights
        self.sess.opt_state = None
        rec: dict = {"loss": []}
        with CompileWatch() as warm:
            for i in range(FIRST_STEPS):
                t0 = time.perf_counter()
                rec["loss"].append(self.step(seed, i))
                if i == 0:
                    self.spans.totals["first_step"] = \
                        time.perf_counter() - t0
                    rec["grad"] = first_grad_norms(
                        self.sess, self.tr["optimizer"]["b1"])
            rec["change"] = change_norms(self.sess, self.make(lo, hi))
        log(f"first steps: losses {rec['loss']}; compiled "
            f"{len(warm.compiled)} executable(s) {warm.compiled}; host "
            f"{host_memory()}")
        return rec

    def release(self) -> None:
        self.sess.weights, self.sess.opt_state = {}, None
        gc.collect()


def compare(cell: dict, seed: int, prog: dict, log=print):
    """The reference's record of ``seed``'s first steps against the
    program's: ``(correct, numbers)``."""
    from bench import check

    m, tr = cell["model"], cell["traffic_mix"]
    t0 = time.perf_counter()
    ref = reference_record(cell["arch"], m, tr["optimizer"], seed,
                           tr["batch"], tr["seq"], log=log)
    log(f"reference: {time.perf_counter() - t0:.1f} s; host {host_memory()}")
    nums = check.numbers(prog, ref)
    ok, _ = check.verdict(nums, cell["limits"])
    return ok, nums


def run(cell: dict, seed: int, seconds: float, trace: bool = False, *,
        require_tpu: bool = True, log=print) -> tuple[dict, dict]:
    """One run of ``cell``; returns the result line's object and the
    numbers compared (``{name: (value, where)}``)."""
    import jax

    from bench import trace as tracing

    devs = devices_for(cell["chips"], require_tpu)
    kind = devs[0].device_kind
    r = Run(cell=cell, model=cell["model"], traffic=cell["traffic_mix"],
            chips=cell["chips"],
            peaks=device_peaks(kind) if devs[0].platform == "tpu" else None)
    log(f"device: {kind} ({devs[0].platform}), {len(devs)} chip(s) used "
        f"of {len(jax.devices())}; compile cache {compile_cache()}; "
        f"training FLOPs per step {r.flops_per_step!r}")
    spans = Spans()
    trainer = Trainer(cell, devs, spans, log)
    with instrumented(spans, trainer.lw):
        prog = trainer.start(seed, log)
        r.setup_spans = dict(spans.totals)
        spans.totals.clear()
        stop_trace = trace_window(trace)
        losses, marks = [], []
        ticks0, use0 = cpu_ticks(), resource.getrusage(resource.RUSAGE_SELF)
        with CompileWatch() as watch, GcWatch() as gcw:
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                t0 = time.perf_counter()
                r.setup_s = t0 - T_START
                while True:
                    losses.append(trainer.step(seed,
                                               FIRST_STEPS + len(losses)))
                    t1 = time.perf_counter()
                    marks.append((t1, dict(spans.totals)))
                    if t1 - t0 >= seconds:
                        break
        r.trace = stop_trace()
    r.window_s, r.steps = t1 - t0, len(losses)
    r.tokens = r.steps * r.traffic["batch"] * r.traffic["seq"]
    r.step_spans = dict(spans.totals)
    log(f"window: {r.steps} step(s) in {r.window_s:.3f} s; compiled "
        f"{len(watch.compiled)} executable(s) in it {watch.compiled}; host "
        f"{host_memory()}")
    log_window_noise(t0, marks, ticks0, use0, gcw, log)
    if watch.compiled:
        raise BenchError(f"the window compiled {watch.compiled}")
    r.memory_peak_bytes = memory_peak(devs)
    log(f"device memory: {devs[0].memory_stats()}")
    trainer.release()
    del trainer
    gc.collect()
    log(f"released: host {host_memory()}")

    ok, nums = compare(cell, seed, prog, log)
    failed = sum(not np.isfinite(x) for x in losses)
    metrics = read_metrics(cell["per_layer" if trace else "end_to_end"], r)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": r.memory_peak_bytes}
    result = {"correct": bool(ok and failed == 0), "attempted": r.steps,
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace and r.trace:
        device.update(busy_s=r.trace["busy_s"],
                      window_s=r.trace["window_s"])
        result["breakdown"] = tracing.breakdown(r.trace)
    result["checks"] = {n: {"value": nums[n][0], "limit": lim}
                        for n, lim in cell["limits"].items()}
    return result, nums


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result, nums = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, (value, at) in nums.items():
        limit = cell["limits"].get(name)
        print(f"check {name} {value!r} limit {limit!r} ({at})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
