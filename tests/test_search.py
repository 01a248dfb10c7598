"""Automated strategy search subsystem (`repro.search`): enumeration
determinism, pruning soundness, cost-model ranking, and execution
validation on CPU fixtures (the simulator's re-priced parallel
makespans must order candidates the way the cost model predicted).

The sim <-> jax bit-exactness of the validated winners runs in the
subprocess selftest (``search:hetero/4`` in ``tests/test_runtime.py``);
everything here is single-process.
"""

import numpy as np
import pytest

from repro.core.costmodel import feasible, memory_per_rank
from repro.search import (CPU_A, SearchError, Searcher, balanced_stages,
                          cpu_cluster, cpu_hetero_cluster,
                          enumerate_candidates, executable_microbatches,
                          proportional_split, proxy_program, prune, rank,
                          tiny_spec, validate)


def homog_searcher(**kw):
    """The homogeneous CPU fixture grid validated in the selftest.

    TP=2 candidates are in the grid: class-vectorized simulator
    dispatch (one stacked numpy call per specialization class, timed
    once and attributed per device) prices a TP shard at its parallel
    share instead of n x python dispatch, so TP measurements carry a
    real ordering signal now."""
    args = dict(global_batch=8, seq_len=128, tp_options=(1, 2),
                pp_options=(1, 2, 4), virtual_options=(1, 2),
                include_hetero=False)
    args.update(kw)
    return Searcher(tiny_spec(), **args)


def hetero_searcher(**kw):
    args = dict(global_batch=8, seq_len=128, tp_options=(1, 2),
                pp_options=(1, 2), pipeline_options=(1, 2),
                virtual_options=(1,))
    args.update(kw)
    return Searcher(tiny_spec(), **args)


# -- space -------------------------------------------------------------------

def test_enumeration_deterministic():
    """Same inputs -> identical candidate sequence; option tuples are
    order-insensitive (sorted grids)."""
    cluster, model = cpu_hetero_cluster(2, 2), tiny_spec()
    a = enumerate_candidates(cluster, model, global_batch=8,
                             tp_options=(1, 2), pp_options=(1, 2, 4),
                             pipeline_options=(1, 2))
    b = enumerate_candidates(cluster, model, global_batch=8,
                             tp_options=(2, 1), pp_options=(4, 2, 1),
                             pipeline_options=(2, 1))
    assert [c.name for c in a] == [c.name for c in b]
    assert len({c.name for c in a}) == len(a)  # names are unique
    # and stable across calls
    c = enumerate_candidates(cluster, model, global_batch=8,
                             tp_options=(1, 2), pp_options=(1, 2, 4),
                             pipeline_options=(1, 2))
    assert [x.describe() for x in a] == [x.describe() for x in c]


def test_proportional_split_never_starves():
    assert proportional_split([100.0, 1.0, 1.0, 1.0], 4) == [1, 1, 1, 1]
    assert sum(proportional_split([3.0, 1.0], 8)) == 8
    assert min(proportional_split([100.0, 1.0], 3)) >= 1
    with pytest.raises(ValueError):
        proportional_split([1.0] * 5, 4)


def test_balanced_stages_regression():
    """The old ``scenarios.search._balanced_stages`` emitted zero-layer
    stages when the group count approached the layer count; the fixed
    version gives every stage >= 1 layer and covers exactly."""
    from repro.scenarios.search import _balanced_stages
    groups = [((0,), 100.0), ((1,), 1.0), ((2,), 1.0), ((3,), 1.0)]
    stages = _balanced_stages(groups, 4)
    assert [st.n_layers for st in stages] == [1, 1, 1, 1]
    covered = sorted(l for st in stages for l in range(*st.layers))
    assert covered == list(range(4))
    assert _balanced_stages is balanced_stages


# -- prune -------------------------------------------------------------------

def test_pruning_sound():
    """Every survivor is genuinely feasible (disjoint ranks, full layer
    cover, under the memory cap); every rejection carries a rule."""
    cluster, model = cpu_cluster(8), tiny_spec()
    cands = enumerate_candidates(cluster, model, global_batch=8,
                                 tp_options=(1, 2, 4),
                                 pp_options=(1, 2, 4, 8))
    report = prune(cluster, model, cands)
    assert report.n_candidates == len(cands)
    assert len(report.survivors) + len(report.rejections) == len(cands)
    for cand in report.survivors:
        strat = cand.strategy
        assert strat is not None
        assert feasible(cluster, model, strat)
        seen = set()
        for p in strat.pipelines:
            covered = sorted(l for st in p.stages
                             for l in range(*st.layers))
            assert covered == list(range(model.n_layers)), cand.name
            for st in p.stages:
                assert not (seen & set(st.ranks)), cand.name
                seen.update(st.ranks)
        for gb in memory_per_rank(model, strat).values():
            assert gb <= 0.85 * CPU_A.mem_gb
    for rej in report.rejections:
        assert rej.rule in ("divisibility", "layer-count", "memory")
        assert rej.reason
    assert "feasible" in report.summary()


def test_search_error_reports_per_rule_counts():
    """An infeasible search raises the structured SearchError (a
    RuntimeError subclass) with per-rule rejection counts."""
    searcher = homog_searcher(tp_options=(16,))
    with pytest.raises(SearchError) as ei:
        searcher.search(cpu_cluster(4))
    err = ei.value
    assert isinstance(err, RuntimeError)
    assert "divisibility" in str(err)
    counts = err.report.counts()
    assert counts["divisibility"] > 0
    assert sum(counts.values()) == len(err.report.rejections)


def test_scenarios_shim_raises_search_error():
    """The legacy scenarios.search entry point surfaces the structured
    error (old callers caught bare RuntimeError — still works)."""
    from repro.scenarios.search import search_hetero_strategy
    with pytest.raises(RuntimeError) as ei:
        search_hetero_strategy(cpu_hetero_cluster(2, 2), tiny_spec(),
                               list(range(4)), 8, 128,
                               tp_options=(32,))
    assert isinstance(ei.value, SearchError)
    assert ei.value.report.counts()["divisibility"] > 0


# -- rank --------------------------------------------------------------------

def test_rank_is_sorted_and_deterministic():
    cluster, model = cpu_cluster(4), tiny_spec()
    report = prune(cluster, model, enumerate_candidates(
        cluster, model, global_batch=8, tp_options=(1, 2),
        pp_options=(1, 2), include_hetero=False))
    ranked = rank(cluster, model, report.survivors, 128)
    times = [rc.predicted_step_s for rc in ranked]
    assert times == sorted(times)
    again = rank(cluster, model, report.survivors, 128)
    assert [rc.name for rc in again] == [rc.name for rc in ranked]
    for rc in ranked:
        assert rc.predicted_step_s == pytest.approx(
            rc.pipeline_s + rc.sync_s)
        assert rc.fwd_fraction is not None  # measured proxy fraction


def test_measured_fwd_fraction_changes_pricing():
    from repro.search.rank import proxy_fwd_fraction, resolve_fwd_fraction
    frac = proxy_fwd_fraction()
    assert 0.0 < frac < 1.0
    assert frac != pytest.approx(1.0 / 3.0)   # not the analytic split
    assert resolve_fwd_fraction(None) is None
    assert resolve_fwd_fraction("measured") == frac
    assert resolve_fwd_fraction(0.25) == 0.25


# -- execution validation ----------------------------------------------------

def test_hetero_proxy_exercises_splitar_grad_path():
    """A hetero (hsize>1) candidate's proxy trains through the SplitAR
    gradient reduction — the api:train/hetero4 path.  (tp pinned to 1:
    with TP=2 in the grid the predicted best reduces grads via plain
    AR, and this test is about the SplitAR plan kind.)"""
    result = hetero_searcher(tp_options=(1,)).search(
        cpu_hetero_cluster(2, 2))
    best = result.best.candidate
    assert best.kind == "hetero"
    proxy = proxy_program(best, n_pairs=8, d=16, f=32, batch=16)
    tplan = proxy.program.compile_train(best.name)
    kinds = {rc.plan.kind for rc in tplan.specialization.resolved}
    assert any("SplitAR" in k for k in kinds), kinds


def test_executable_microbatches_respects_shape():
    result = homog_searcher().search(cpu_cluster(4))
    by_name = {rc.name: rc.candidate for rc in result.ranked}
    assert executable_microbatches(by_name["dp4.tp1.pp1"], 64) <= 2
    v2 = by_name["dp1.tp1.pp4.v2"]
    m = executable_microbatches(v2, 64)
    assert m % v2.pp == 0 or m <= v2.pp
    assert 64 % m == 0


@pytest.mark.parametrize("n", [2, 4, 8])
def test_rank_agreement_homogeneous(n):
    """Predicted ordering vs re-priced executed makespans on an n-rank
    homogeneous CPU mesh: pairwise concordance must be high (ties within
    5% carry no ordering signal and are not counted against).

    Per-tier shapes keep the measurement in its valid regime: every
    candidate needs m >= 2 microbatches (a real timetable to re-price,
    so the global batch grows with the widest DP), and per-op compute
    must dominate python dispatch (n=2 packs the whole pair chain onto
    each device, so its proxy dims are larger)."""
    pp = tuple(p for p in (1, 2, 4) if p <= n)
    gb, d, f = {2: (4, 128, 256), 4: (8, 64, 128),
                8: (16, 64, 128)}[n]
    result = homog_searcher(pp_options=pp, global_batch=gb).search(
        cpu_cluster(n), validate_top=5, repeats=5, batch=64, d=d, f=f)
    val = result.validation
    assert val is not None
    executed = [e for e in val.executed if e.error is None]
    assert len(executed) >= 2, val.summary()
    for e in executed:
        assert e.loss is not None
        assert e.measured_makespan_s and e.measured_makespan_s > 0
    ag = val.agreement()
    assert ag is not None and ag >= 0.8, val.summary()


def test_rank_agreement_heterogeneous():
    """On the two-class fixture the ordering is checked on
    speed-PROJECTED makespans (the CPU mesh runs both classes at equal
    speed; projection reintroduces the priced tflops ratio)."""
    result = hetero_searcher().search(
        cpu_hetero_cluster(2, 2), validate_top=3, repeats=5, batch=64,
        d=64, f=128)
    val = result.validation
    assert val is not None and val.speed_projected
    executed = [e for e in val.executed if e.error is None]
    assert len(executed) == 3, val.summary()
    for e in executed:
        assert e.projected_makespan_s and e.projected_makespan_s > 0
    ag = val.agreement()
    assert ag is not None and ag >= 2 / 3, val.summary()
    assert "agreement" in val.summary()


def test_rank_agreement_tp_winner():
    """Predicted-vs-measured ordering with a TP>=2 WINNER: on a
    TP-only grid every candidate shards the pair chain across devices,
    and the re-priced makespans (stacked-dispatch timings, dt/n per
    device) must still order the candidates the way the cost model
    predicted — the regime the old per-device python dispatch drowned
    out (ROADMAP item 2 pinned ``tp_options=(1,)`` because of it)."""
    result = homog_searcher(tp_options=(2,), pp_options=(1, 2),
                            virtual_options=(1,)).search(
        cpu_cluster(4), validate_top=4, repeats=5, batch=64, d=64, f=128)
    assert result.best.candidate.tp >= 2
    val = result.validation
    assert val is not None
    executed = [e for e in val.executed if e.error is None]
    assert len(executed) >= 2, val.summary()
    for e in executed:
        assert e.loss is not None
        assert e.measured_makespan_s and e.measured_makespan_s > 0
    ag = val.agreement()
    assert ag is not None and ag >= 0.8, val.summary()


def test_interleaved_candidate_validates():
    """A v=2 candidate executes under the interleaved schedule (the only
    schedule a v>1 plan accepts)."""
    result = homog_searcher().search(cpu_cluster(4))
    v2 = next(rc for rc in result.ranked if rc.candidate.v == 2)
    report = validate(cpu_cluster(4), [v2], top_k=1, repeats=2,
                      batch=32, d=32, f=64)
    [e] = report.executed
    assert e.error is None, e.describe()
    assert e.schedule == "interleaved"
    assert e.loss is not None
    assert e.measured_makespan_s and e.measured_makespan_s > 0


def test_searcher_is_restart_free():
    """One Searcher instance serves topology changes without rebuild:
    nothing cluster-specific is cached (the elastic driver contract)."""
    searcher = hetero_searcher()
    r44 = searcher.search(cpu_hetero_cluster(2, 2))
    r2 = searcher.search(cpu_cluster(2))
    r44b = searcher.search(cpu_hetero_cluster(2, 2))
    assert [rc.name for rc in r44.ranked] == \
        [rc.name for rc in r44b.ranked]
    assert {rc.predicted_step_s for rc in r44.ranked} == \
        {rc.predicted_step_s for rc in r44b.ranked}
    # the 2-rank cluster admits a different (smaller) candidate set
    assert {rc.name for rc in r2.ranked} != \
        {rc.name for rc in r44.ranked}
    for rc in r2.ranked:
        assert rc.candidate.n_devices <= 2


def test_searcher_select_considers_extras():
    from repro.core.costmodel import step_time
    searcher = homog_searcher()
    cluster = cpu_cluster(4)
    best = searcher.select(cluster)
    searched = searcher.search(cluster).best
    assert step_time(cluster, searcher.model, best, searcher.seq_len) \
        == step_time(cluster, searcher.model,
                     searched.candidate.strategy, searcher.seq_len)
    # an extra strictly better than every searched candidate wins
    fake = searched.candidate.strategy
    assert searcher.select(cluster, extras=(fake,)) is not None


# -- session / plan measurement hooks ---------------------------------------

def test_measure_train_step_and_recorded_ticks():
    from repro import api
    from repro.api.testing import loss_pipeline_program, \
        loss_pipeline_values
    from repro.runtime.telemetry import recent_steps

    prog = loss_pipeline_program(2, name="pipe2")
    xv, ws, want_y = loss_pipeline_values(seed=11)
    ex = api.SimulatorExecutor(record_ticks=True)
    sess = api.Session(prog, "pipe2", executor=ex)
    sess.load(ws)
    sess.train_step({"X": xv}, num_microbatches=4)
    r = sess.train_step({"X": xv}, num_microbatches=4)
    # the first step already applied an optimizer update, so the
    # second step's loss has moved off the fresh-weights value
    assert np.isfinite(r.loss)
    rec = recent_steps()[-1]
    assert rec.updates == 2 and rec.seconds > 0
    assert rec.spans["optimizer"] <= rec.seconds
    assert ex.last_tick_device_seconds
    for (stage, phase), occurrences in ex.last_tick_device_seconds.items():
        assert phase in ("fwd", "bwd")
        for devops in occurrences:
            for dev, samples in devops.items():
                assert all(s >= 0 for s in samples)


def test_predicted_step_seconds_units():
    from repro.api.testing import loss_pipeline_program

    prog = loss_pipeline_program(2, name="pipe2")
    tplan = prog.compile_train("pipe2")
    base = tplan.predicted_step_seconds(4, "1f1b")
    assert base > 0
    # FLOPs-derived: doubling device speed halves the makespan
    half = tplan.predicted_step_seconds(4, "1f1b",
                                        flops_per_second=2e12)
    assert half == pytest.approx(base / 2)


def test_simulator_executor_rejects_unknown_kwargs():
    from repro import api
    with pytest.raises(TypeError):
        api.get_executor("sim", bogus=True)
    ex = api.get_executor("sim", record_ticks=True)
    assert ex.record_ticks


# -- scenario integration ----------------------------------------------------

def test_priced_schedule_stats_measured_fraction():
    from repro.core.costmodel import LLAMA_32B, paper_cluster
    from repro.scenarios.hetero import (hetu_32b_16h800_16h20,
                                        priced_schedule_stats)
    cluster = paper_cluster(16, 16)
    strat = hetu_32b_16h800_16h20()
    analytic = priced_schedule_stats(cluster, LLAMA_32B, strat, 4096)
    measured = priced_schedule_stats(cluster, LLAMA_32B, strat, 4096,
                                     fwd_fraction="measured")
    assert len(analytic) == len(measured) == len(strat.pipelines)
    assert any(a.makespan != m.makespan
               for a, m in zip(analytic, measured))


def test_elastic_trace_with_searcher_reselection():
    """run_trace re-selects per config through Searcher.select (the
    hand-written layout competes as an extra) and measured pricing
    changes the step times."""
    from repro.core.costmodel import ClusterSpec, H20
    from repro.scenarios.elastic import run_trace
    cluster = ClusterSpec((H20,) * 8)
    trace = [("C1", list(range(8))), ("C2", list(range(6)))]
    base = run_trace(trace, cluster, tiny_spec(), global_batch=8,
                     seq_len=128)
    measured = run_trace(trace, cluster, tiny_spec(), global_batch=8,
                         seq_len=128, pricing="measured")
    assert [r.name for r in base] == ["C1", "C2"]
    assert any(b.step_time_s != m.step_time_s
               for b, m in zip(base, measured))
    searcher = Searcher(tiny_spec(), global_batch=8, seq_len=128,
                        tp_options=(1, 2), pp_options=(1, 2),
                        pipeline_options=(1, 2))
    picked = run_trace(trace, cluster, tiny_spec(), global_batch=8,
                       seq_len=128, searcher=searcher)
    # the searched strategies can only improve on the fixture layout
    for fix, srch in zip(base, picked):
        assert srch.step_time_s <= fix.step_time_s * 1.001
