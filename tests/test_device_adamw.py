"""AdamW on the device (``optim.adamw.sharded_apply_updates`` over
``Blocks``) against the same update over host shards.

The multi-device cases run once in a child process with 4 forced host
CPU devices (this process keeps its default device count) and report
per case; the rest run here on one device through a JaxExecutor, whose
unpipelined step keeps m/v on the device.
"""

import json
import textwrap

import numpy as np
import pytest

from repro import api
from repro.core.annotations import DS, spmd
from repro.core.simulator import gather
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.runtime import telemetry

_CHILD = textwrap.dedent("""
    import json, traceback
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro import api
    from repro.core.annotations import DS, DUP, HSPMD, spmd
    from repro.core.simulator import gather, scatter
    from repro.elastic import ElasticDriver, Fault, FaultPlan
    from repro.elastic.fixtures import (probe_feeds, probe_graph,
                                        probe_layout, probe_provider,
                                        probe_values, reference_run)
    from repro.optim import adamw
    from repro.runtime.lowering import DeviceOrder

    mesh = Mesh(np.array(jax.devices()[:4]), ("dev",))
    cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=3, weight_decay=0.1)
    cases = {}

    def same(a, b, what):
        for dev in a.parts:
            np.testing.assert_array_equal(np.asarray(b.parts[dev]),
                                          np.asarray(a.parts[dev]),
                                          err_msg=f"{what} dev {dev}")

    def blocks_case(annots):
        # the same update, host shards against blocks on the mesh, over
        # steps whose gradients are clipped and not
        shapes = {n: s for n, (_, s) in annots.items()}
        rng = np.random.default_rng(5)
        values = {n: rng.normal(size=s).astype(np.float32)
                  for n, s in shapes.items()}
        host = {n: scatter(values[n], a) for n, (a, _) in annots.items()}
        items = tuple((n, annots[n][0], shapes[n]) for n in sorted(annots))
        lay = adamw.block_layout(DeviceOrder((0, 1, 2, 3)), 4, mesh, items)
        dev = lay.put(host)
        st_h = adamw.init_sharded_state(host)
        st_d = adamw.init_sharded_state(dev)
        norms = []
        for step, size in enumerate((3.0, 0.01, 1.0)):
            grads = {n: scatter((rng.normal(size=s) * size)
                                .astype(np.float32), annots[n][0])
                     for n, s in shapes.items()}
            host, st_h, mh = adamw.sharded_apply_updates(host, grads, st_h,
                                                         cfg)
            dev, st_d, md = adamw.sharded_apply_updates(
                dev, lay.put(grads), st_d, cfg)
            assert mh == md, (step, mh, md)
            dense = np.sqrt(sum(np.sum(np.square(gather(g)), dtype=np.float64)
                                for g in grads.values()))
            assert abs(mh["grad_norm"] - dense) <= 1e-6 * dense, (mh, dense)
            norms.append(mh["grad_norm"])
            for key, h, d in (("p", host, dev), ("m", st_h["m"], st_d["m"]),
                              ("v", st_h["v"], st_d["v"])):
                got = d.host()
                for n in shapes:
                    same(h[n], got[n], f"step {step} {key} {n}")
        assert norms[0] > cfg.grad_clip > norms[1], norms
        return {"grad_norms": norms,
                "padded_leaves": sorted(lay.names[i] for i in lay.bounds)}

    def dp_tp():
        devs = [0, 1, 2, 3]
        return blocks_case({
            "w_col": (spmd(devs, DS([(DUP, 2), (1, 2)])), (6, 8)),
            "w_row": (spmd(devs, DS([(DUP, 2), (0, 2)])), (8, 6)),
            "w_fsdp": (spmd(devs, DS({0: 4})), (12, 3)),
            "bias": (spmd(devs, DS({DUP: 4})), (7,)),
        })

    def asymmetric():
        # uneven subgroup slabs (6 rows split over 0,1; 4 rows copied on
        # 2,3, padded to 6), leaves that idle half the mesh and a leaf
        # copied across subgroups that split it differently
        uneven = HSPMD(dgs=[[0, 1], [2, 3]], dss=[DS({0: 2}), DS({DUP: 2})],
                       hdim=0, hsplits=(3, 1))
        # a copy per subgroup, split in one and whole in the other
        copies = HSPMD(dgs=[[0, 1], [2, 3]], dss=[DS({1: 2}), DS({DUP: 2})],
                       hdim=DUP)
        return blocks_case({
            "w_uneven": (uneven, (16, 5)),
            "w_copies": (copies, (3, 8)),
            "w_stage0": (spmd([0, 1], DS({1: 2})), (4, 6)),
            "w_stage1": (spmd([2], DS({})), (5, 3)),
        })

    def snap(sess):
        st = sess.opt_state
        out = {n: sess.weights[n] for n in sess.weights}
        out.update({f"m/{n}": s for n, s in st["m"].items()})
        out.update({f"v/{n}": s for n, s in st["v"].items()})
        return out

    def session_switch():
        # device-resident m/v through a switch (host views out, migrated,
        # uploaded under the new layout) == the simulator's host path
        got = {}
        for ex in (api.SimulatorExecutor(), api.JaxExecutor(mesh)):
            s_a = probe_layout([0, 1, 2, 3], "dp")
            s_b = probe_layout([0, 1], "pp")
            prog = api.Program(probe_graph(), [s_a, s_b])
            sess = api.Session(prog, s_a.name, executor=ex, optimizer=cfg)
            sess.load(probe_values())
            for step in range(5):
                if step == 2:
                    sess.switch(s_b.name)
                sess.train_step(probe_feeds(step))
            got[ex.name] = snap(sess)
        for key, st in got["sim"].items():
            same(st, got["jax"][key], key)
        return {"compared": sorted(got["sim"])}

    def session_restore():
        # a switch, a checkpoint, a crash and a restore that assigns host
        # shards to opt_state, all with m/v on the device: bitwise on the
        # uninterrupted reference
        import tempfile
        tmp = tempfile.mkdtemp(prefix="device-adamw-")
        faults = FaultPlan((Fault(4, "crash", phase="post-checkpoint"),))
        drv = ElasticDriver(probe_graph(), probe_values(), probe_provider(),
                            probe_feeds, executor=api.JaxExecutor(mesh),
                            num_microbatches=1, checkpoint_every=2,
                            ckpt_dir=tmp, faults=faults)
        trace = [(0, (0, 1, 2, 3), "dp"), (2, (0, 1), "dp")]
        run = drv.run(trace, 6)
        assert run.interrupted_at == 4, run.summary()
        drv.resume(trace, 6, ranks=(2, 3), layout="pp")
        ref, _ = reference_run(probe_layout([0, 1, 2, 3], "dp"), 6)
        want, have = snap(ref), snap(drv.session)
        for key in want:
            np.testing.assert_array_equal(
                gather(have[key]), gather(want[key]), err_msg=key)
        return {"steps": 6}

    for name, fn in (("blocks/dp_tp", dp_tp),
                     ("blocks/asymmetric", asymmetric),
                     ("session/switch", session_switch),
                     ("session/restore", session_restore)):
        try:
            cases[name] = {"ok": True, **fn()}
        except Exception as e:
            cases[name] = {"ok": False, "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc(limit=6)}
    print("DEVICE_ADAMW_JSON " + json.dumps(cases))
""")

CASES = ["blocks/dp_tp", "blocks/asymmetric", "session/switch",
         "session/restore"]


@pytest.fixture(scope="module")
def report():
    from repro.runtime.harness import run_subprocess

    proc = run_subprocess(_CHILD, n_devices=4, timeout=300)
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICE_ADAMW_JSON "):
            return json.loads(line[len("DEVICE_ADAMW_JSON "):])
    pytest.fail(f"no report (rc={proc.returncode}):\n{proc.stdout}\n"
                f"{proc.stderr}")


@pytest.mark.parametrize("case", CASES)
def test_device_update_matches_host_update(report, case):
    """Bit for bit on 4 devices: the update over blocks against the one
    over host shards (dp x tp, and uneven slabs with idle rows), and
    sessions whose m/v live on the device through a switch and an
    elastic restore."""
    got = report[case]
    assert got["ok"], f"{case}: {got.get('error')}\n{got.get('trace')}"
    if case == "blocks/asymmetric":
        assert got["padded_leaves"] == ["w_copies", "w_uneven"]


# ---------------------------------------------------------------------------
# one device, in this process
# ---------------------------------------------------------------------------

def _program():
    g = api.Graph()
    g.placeholder("X", (8, 6))
    g.parameter("W1", (6, 5))
    g.parameter("W2", (5, 3))
    h = g.gelu(g.dot(g.tensors["X"], g.tensors["W1"]), name="H")
    y = g.dot(h, g.tensors["W2"], name="Y")
    g.sum(g.sum(y, 1), 0, name="L")
    one = spmd([0], DS({}))
    return api.Program(g, [api.Strategy("one", {
        "X": one, "W1": one, "W2": one})])


def _values(seed=7):
    rng = np.random.default_rng(seed)
    return {"W1": rng.normal(size=(6, 5)).astype(np.float32),
            "W2": rng.normal(size=(5, 3)).astype(np.float32)}


def _feeds(step):
    rng = np.random.default_rng(100 + step)
    return {"X": rng.normal(size=(8, 6)).astype(np.float32)}


CFG = AdamWConfig(lr=1e-2, warmup_steps=100, weight_decay=0.1)


def _session(executor=None, prog=None):
    sess = api.Session(prog or _program(), "one",
                       executor=executor or api.JaxExecutor(), optimizer=CFG)
    sess.load(_values())
    return sess


def _weights(sess):
    return {n: sess.weight_value(n) for n in sess.weights}


def test_no_compile_after_the_first_step():
    """Five warm-up steps (a new learning rate each) after the first
    compile nothing: the step's scalars are arguments."""
    import jax

    sess = _session()
    sess.train_step(_feeds(0))
    compiled = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        lrs = [sess.train_step(_feeds(s)).metrics["lr"]
               for s in range(1, 6)]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiled == []
    assert len(set(lrs)) == 5


def test_returning_the_given_params_keeps_weights(monkeypatch):
    """An update that hands back the weights it was given leaves them
    as loaded and the session runnable: only m and v are donated."""
    orig = adamw.sharded_apply_updates

    def frozen(params, grads, state, cfg):
        _, state, metrics = orig(params, grads, state, cfg)
        return params, state, metrics

    monkeypatch.setattr(adamw, "sharded_apply_updates", frozen)
    sess = _session()
    losses = [sess.train_step(_feeds(0)).loss for _ in range(3)]
    assert losses[0] == losses[1] == losses[2]
    for n, w in _values().items():
        np.testing.assert_array_equal(sess.weight_value(n), w)
    assert sess.opt_state["count"] == 3


def test_opt_state_reads_host_views_and_takes_a_dict():
    """Reading gives host shards sharded like the weights; a session
    given that dict and those weights continues bit for bit."""
    a = _session()
    for s in range(2):
        a.train_step(_feeds(s))
    st = a.opt_state
    assert st["count"] == 2
    for n, w in a.weights.items():
        m = st["m"][n]
        assert isinstance(m.parts[0], np.ndarray)
        assert m.annot == w.annot and m.parts[0].shape == w.parts[0].shape
    b = _session()
    b.load({n: a.weight_value(n) for n in a.weights})
    b.opt_state = st
    for s in range(2, 4):
        a.train_step(_feeds(s))
        b.train_step(_feeds(s))
    for n in a.weights:
        np.testing.assert_array_equal(b.weight_value(n), a.weight_value(n))
        np.testing.assert_array_equal(gather(b.opt_state["v"][n]),
                                      gather(a.opt_state["v"][n]))


def test_opt_state_none_starts_afresh():
    """Assigning None drops the state: the next step is a first step."""
    a = _session()
    a.train_step(_feeds(0))
    w = _weights(a)
    a.opt_state = None
    assert a.opt_state is None
    r = a.train_step(_feeds(1))
    b = _session()
    b.load(w)
    want = b.train_step(_feeds(1))
    assert r.metrics == want.metrics
    for n in w:
        np.testing.assert_array_equal(a.weight_value(n), b.weight_value(n))
    assert a.opt_state["count"] == 1


def test_grads_are_fetched_when_read():
    """The device step fetches the loss and the new weights; the
    gradients stay on the device until ``grads`` is read, and then
    equal the reference gradient."""
    import jax
    import jax.numpy as jnp

    sess = _session()
    r = sess.train_step(_feeds(0))
    rec = telemetry.recent_steps()[-1]
    params = sum(v.size for v in _values().values())
    assert rec.counts["d2h_bytes"] == 4 * params + 4   # weights + loss
    assert callable(r._grads)
    x = _feeds(0)["X"]

    def loss(p):
        h = jax.nn.gelu(x @ p["W1"], approximate=True)
        return jnp.sum(h @ p["W2"])

    want = jax.grad(loss)({n: jnp.asarray(v) for n, v in _values().items()})
    for n, g in want.items():
        np.testing.assert_allclose(r.grad_value(n), g, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("where", ["device", "host"])
def test_step_record_counts_where_the_update_ran(where):
    """``opt_device_leaves``/``opt_host_leaves`` count the leaves each
    way, and ``device_state_bytes`` is m and v at their padded block
    shapes (zero when the host holds them)."""
    ex = api.JaxExecutor() if where == "device" else \
        api.SimulatorExecutor()
    sess = _session(ex)
    for s in range(2):
        sess.train_step(_feeds(s))
    rec = telemetry.recent_steps()[-1]
    n = len(_values())
    want = 2 * 4 * sum(v.size for v in _values().values()) \
        if where == "device" else 0
    assert rec.counts.get(f"opt_{where}_leaves") == n
    other = "host" if where == "device" else "device"
    assert f"opt_{other}_leaves" not in rec.counts
    assert rec.gauges["device_state_bytes"] == want


def test_device_state_bytes_on_the_tiny_cell():
    """The benchmark's cell at CPU size: every leaf updated on the
    device, and its m/v bytes exact from the pad shapes."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "bench"))
    from tiny_cell import tiny_cell

    from bench import run
    from repro.runtime.lowering import pad_shape

    trainer = run.Trainer(tiny_cell(), run.devices_for(1, require_tpu=False),
                          run.Spans(), log=lambda *_: None)
    trainer.start(2**31 + 7, log=lambda *_: None)
    rec = telemetry.recent_steps()[-1]
    lw = trainer.lw
    params = [t.name for t in lw.graph.parameters()]
    pads = sum(lw.n_mesh * int(np.prod(pad_shape(
        lw.graph.tensors[n].annots[lw.k], lw.shapes[n]))) for n in params)
    trainer.release()
    assert rec.counts["opt_device_leaves"] == len(params)
    assert "opt_host_leaves" not in rec.counts
    assert rec.gauges["device_state_bytes"] == 2 * 4 * pads


def test_apply_updates_shares_the_leaf_step():
    """The pytree update is :func:`adamw_leaf` over its leaves: one
    step against the leaf function applied by hand."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    params = {"a": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
    grads = {"a": jnp.asarray(rng.normal(size=(4, 3)) * 5, jnp.float32)}
    new, st, met = adamw.apply_updates(params, grads,
                                       adamw.init_opt_state(params), CFG)
    scale = jnp.minimum(1.0, CFG.grad_clip / (met["grad_norm"] + 1e-9))
    p, m, v = adamw.adamw_leaf(params["a"], grads["a"], 0.0, 0.0, scale,
                               met["lr"], 1 - CFG.b1, 1 - CFG.b2, CFG.b1,
                               1 - CFG.b1, CFG.b2, 1 - CFG.b2, CFG.eps,
                               CFG.weight_decay)
    np.testing.assert_array_equal(new["a"], p)
    np.testing.assert_array_equal(st["m"]["a"], m)
    np.testing.assert_array_equal(st["v"]["a"], v)
