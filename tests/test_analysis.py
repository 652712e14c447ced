"""Tests for the static-analysis layer (`repro.analysis`).

The core contract under test: corrupt a known-valid artifact one
invariant at a time and the verifier must *name* the violation class
(`VerifyError.kinds`), not merely throw. Plus the IR linter's
contract checks on lowered arrays, the AST repo lint rules, and the
`verify=` integration points (registry, batch engine, online cluster).
"""

import dataclasses
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (IRLintError, VerifyError, lint_batch,
                            lint_graph_arrays, lint_ir,
                            lint_population_arrays, lint_source,
                            verify_batch_result, verify_cluster,
                            verify_schedule, verify_sim_result,
                            verify_timeline)
from repro.core import (Schedule, SynthParams, Timeline,
                        cluster_of_multicores, dell_poweredge_1950,
                        generate_app, hp_bl260c)
from repro.core import lowering
from repro.core.mpaha import AppGraph
from repro.core.registry import (SCHEDULERS, get_scheduler, get_simulator,
                                 register_scheduler)
from repro.core.schedule import Placement
from repro.core.sim_engine import simulate_batch

VOL = 3e9       # ~1 s cross-socket on the Dell model: comm lag >> tolerances


def two_task_graph():
    """sid 0 (10 s) --VOL--> sid 1 (5 s): one comm edge, no chains."""
    g = AppGraph(n_types=1)
    g.add_task(0, [(10.0,)])
    g.add_task(1, [(5.0,)])
    g.add_edge(0, 1, volume=VOL)
    g.finalize()
    return g


def tight_schedule(g, m):
    """The tightest valid plan: consumer starts exactly at end + comm."""
    comm = m.comm_time(VOL, 0, 7)
    s = Schedule(m.n_cores)
    s.place(0, 0, 0.0, 10.0)
    s.place(1, 7, 10.0 + comm, 15.0 + comm)
    return s


def rebuilt(base, override=None, extra=None, skip=()):
    """Copy a schedule with one targeted edit (keeps core_slots sorted)."""
    out = Schedule(base.n_cores)
    for sid, p in base.placements.items():
        if sid in skip:
            continue
        core, start, end = (override or {}).get(sid, (p.core, p.start, p.end))
        out.place(sid, core, start, end)
    for sid, core, start, end in (extra or ()):
        out.place(sid, core, start, end)
    return out


def kinds_of(fn):
    with pytest.raises(VerifyError) as ei:
        fn()
    return ei.value.kinds


# ---------------------------------------------------------------------------
# schedule mutation tests: one invariant broken at a time, named exactly
# ---------------------------------------------------------------------------

def test_valid_schedule_passes():
    g, m = two_task_graph(), dell_poweredge_1950()
    assert verify_schedule(tight_schedule(g, m), g, m, collect=True) == []


def test_detects_dropped_comm_cost():
    g, m = two_task_graph(), dell_poweredge_1950()
    # consumer starts at the producer's end: precedence holds, comm dropped
    bad = rebuilt(tight_schedule(g, m), override={1: (7, 10.0, 15.0)})
    assert kinds_of(lambda: verify_schedule(bad, g, m)) == {"comm"}


def test_detects_precedence_flip():
    g, m = two_task_graph(), dell_poweredge_1950()
    bad = rebuilt(tight_schedule(g, m), override={1: (7, 4.0, 9.0)})
    assert kinds_of(lambda: verify_schedule(bad, g, m)) == {"precedence"}


def test_detects_overlap():
    g, m = two_task_graph(), dell_poweredge_1950()
    # consumer shoved onto the producer's core, mid-interval
    bad = rebuilt(tight_schedule(g, m), override={1: (0, 5.0, 10.0)})
    assert "overlap" in kinds_of(lambda: verify_schedule(bad, g, m))


def test_detects_stale_extra_sid():
    g, m = two_task_graph(), dell_poweredge_1950()
    bad = rebuilt(tight_schedule(g, m), extra=[(99, 2, 0.0, 1.0)])
    assert kinds_of(lambda: verify_schedule(bad, g, m)) == {"namespace"}


def test_detects_missing_sid():
    g, m = two_task_graph(), dell_poweredge_1950()
    bad = rebuilt(tight_schedule(g, m), skip=(1,))
    assert kinds_of(lambda: verify_schedule(bad, g, m)) == {"namespace"}


def test_detects_duration_mismatch():
    g, m = two_task_graph(), dell_poweredge_1950()
    comm = m.comm_time(VOL, 0, 7)
    bad = rebuilt(tight_schedule(g, m),
                  override={1: (7, 10.0 + comm, 12.0 + comm)})
    assert kinds_of(lambda: verify_schedule(bad, g, m)) == {"duration"}


def test_detects_core_out_of_range():
    g, m = two_task_graph(), dell_poweredge_1950()
    bad = tight_schedule(g, m)
    bad.placements[1].core = 42         # machine has 8
    assert "core-range" in kinds_of(lambda: verify_schedule(bad, g, m))


def test_detects_release_violation():
    g, m = two_task_graph(), dell_poweredge_1950()
    sch = tight_schedule(g, m)          # sid 0 starts at 0.0
    assert "release" in kinds_of(
        lambda: verify_schedule(sch, g, m, release_floor=1.0))
    assert "release" in kinds_of(
        lambda: verify_schedule(sch, g, m, releases={0: 2.5}))


def test_detects_task_split():
    m = dell_poweredge_1950()
    g = AppGraph(n_types=1)
    g.add_task(0, [(3.0,), (4.0,)])     # one task, chained subtasks
    g.finalize()
    comm = m.comm_time(0.0, 0, 1)       # chain edges still pay latency
    s = Schedule(m.n_cores)
    s.place(0, 0, 0.0, 3.0)
    s.place(1, 1, 3.0 + comm, 7.0 + comm)
    assert kinds_of(lambda: verify_schedule(s, g, m)) == {"task-coherence"}
    # the AMTHA coherence rule is opt-out for HEFT/ETF-style schedulers
    assert verify_schedule(s, g, m, require_task_coherence=False,
                           collect=True) == []


def test_collect_reports_every_violation_together():
    g, m = two_task_graph(), dell_poweredge_1950()
    bad = rebuilt(tight_schedule(g, m), override={1: (7, 4.0, 9.0)},
                  extra=[(99, 2, 0.0, 1.0)])
    out = verify_schedule(bad, g, m, collect=True)
    assert {v.kind for v in out} == {"precedence", "namespace"}
    with pytest.raises(VerifyError) as ei:
        verify_schedule(bad, g, m)
    assert len(ei.value.violations) == len(out)


def test_sid_offset_shifts_namespace():
    g, m = two_task_graph(), dell_poweredge_1950()
    comm = m.comm_time(VOL, 0, 7)
    s = Schedule(m.n_cores)
    s.place(10, 0, 0.0, 10.0)
    s.place(11, 7, 10.0 + comm, 15.0 + comm)
    assert verify_schedule(s, g, m, sid_offset=10, collect=True) == []
    assert "namespace" in kinds_of(lambda: verify_schedule(s, g, m))


# ---------------------------------------------------------------------------
# timeline structural verification
# ---------------------------------------------------------------------------

def test_timeline_open_transaction_detected():
    tl = Timeline(2)
    tl.place(0, 0, 0.0, 1.0)
    tl.begin()
    assert "transaction" in kinds_of(lambda: verify_timeline(tl))
    tl.rollback()
    assert verify_timeline(tl, collect=True) == []


def test_timeline_watermark_regression_detected():
    tl = Timeline(2)
    tl.place(0, 0, 0.0, 2.0)
    tl._avail[0] = 0.5                  # below the last interval's end
    assert "structure" in kinds_of(lambda: verify_timeline(tl))


def test_timeline_orphan_placement_detected():
    tl = Timeline(2)
    tl.place(0, 0, 0.0, 1.0)
    tl.placements[5] = Placement(5, 1, 2.0, 3.0)    # not in the arrays
    assert "structure" in kinds_of(lambda: verify_timeline(tl))


def test_timeline_rides_along_in_verify_schedule():
    g, m = two_task_graph(), dell_poweredge_1950()
    tl = Timeline.from_schedule(tight_schedule(g, m))
    assert verify_schedule(tl, g, m, collect=True) == []
    tl.begin()
    assert "transaction" in kinds_of(lambda: verify_schedule(tl, g, m))
    tl.rollback()


# ---------------------------------------------------------------------------
# per-scenario SimResult verification
# ---------------------------------------------------------------------------

def sim_fixture():
    m = dell_poweredge_1950()
    g = generate_app(SynthParams(n_tasks=(6, 9)), seed=11)
    sch = get_scheduler("engine")(g, m)
    res = get_simulator("arrays")(g, m, sch, contention=False)
    return g, res


def test_sim_result_valid_then_each_corruption_named():
    g, res = sim_fixture()
    assert verify_sim_result(res, g, collect=True) == []

    res.t_exec += 1.0
    assert kinds_of(lambda: verify_sim_result(res, g)) == {"makespan"}
    res.t_exec -= 1.0

    sid = max(res.subtask_end)
    res.subtask_end[sid] = np.inf       # not stranded, fault-free
    assert "finite-end" in kinds_of(lambda: verify_sim_result(res, g))

    del res.subtask_end[sid]
    assert "namespace" in kinds_of(lambda: verify_sim_result(res, g))


# ---------------------------------------------------------------------------
# vectorized batch-result verification
# ---------------------------------------------------------------------------

def batch_fixture():
    m = dell_poweredge_1950()
    g = two_task_graph()
    sch = tight_schedule(g, m)
    one = AppGraph(n_types=1)
    one.add_task(0, [(2.0,)])           # 1 subtask -> scenario 1 is padded
    one.finalize()
    s1 = Schedule(m.n_cores)
    s1.place(0, 0, 0.0, 2.0)
    batch = lowering.batch_scenarios([
        lowering.lower_scenario(g, m, sch),
        lowering.lower_scenario(one, m, s1)])
    res = simulate_batch(batch, verify=True)        # lint + verify pass
    return batch, res


def batch_kinds(batch, res, edits):
    end = np.array(res.subtask_end)
    t_exec = np.array(res.t_exec)
    for (i, j), v in edits.items():
        end[i, j] = v
    t_exec[0] = np.where(np.isfinite(end[0]), end[0], 0.0).max()
    bad = dataclasses.replace(res, subtask_end=end, t_exec=t_exec)
    with pytest.raises(VerifyError) as ei:
        verify_batch_result(batch, bad)
    return ei.value.kinds


def test_batch_detects_dropped_comm_lag():
    batch, res = batch_fixture()
    end0 = res.subtask_end[0, 0]
    lag = batch.pred_lat[0, 1, 0] + batch.pred_volbw[0, 1, 0]
    assert lag > 1e-3                   # VOL makes the lag macroscopic
    # meets precedence (pred end + duration) but lands inside the lag
    kinds = batch_kinds(batch, res,
                        {(0, 1): end0 + batch.duration[0, 1] + lag / 2})
    assert kinds == {"comm"}


def test_batch_detects_precedence_violation():
    batch, res = batch_fixture()
    kinds = batch_kinds(batch, res, {(0, 1): 12.0})     # < end0 + dur = 15
    assert kinds == {"precedence"}


def test_batch_detects_touched_padding():
    batch, res = batch_fixture()
    end = np.array(res.subtask_end)
    end[1, 1] = 3.14                    # scenario 1 has only 1 real subtask
    bad = dataclasses.replace(res, subtask_end=end)
    with pytest.raises(VerifyError) as ei:
        verify_batch_result(batch, bad)
    assert ei.value.kinds == {"padding"}


def test_batch_detects_makespan_mismatch():
    batch, res = batch_fixture()
    bad = dataclasses.replace(res, t_exec=np.array(res.t_exec) + 1.0)
    with pytest.raises(VerifyError) as ei:
        verify_batch_result(batch, bad)
    assert ei.value.kinds == {"makespan"}


def test_batch_detects_nonfinite_end_without_faults():
    batch, res = batch_fixture()
    end = np.array(res.subtask_end)
    end[0, 1] = np.inf
    bad = dataclasses.replace(res, subtask_end=end)
    with pytest.raises(VerifyError) as ei:
        verify_batch_result(batch, bad)
    assert "finite-end" in ei.value.kinds


def join_fixture():
    """A 4 x 4 Montage: its mConcatFit reads 33 fits through 2 join rows."""
    from repro.core.workflows import montage
    m = hp_bl260c()
    wf = montage(4, 1)
    sch = get_scheduler("engine")(wf, m)
    batch = lowering.batch_scenarios([lowering.lower_scenario(wf, m, sch)])
    res = simulate_batch(batch, verify=True)
    return batch, res


def test_batch_detects_corrupted_join_row():
    batch, res = join_fixture()
    j = int(batch.n_sub[0])                 # first join row
    assert batch.n_rows[0] == j + 2
    end = np.array(res.subtask_end)
    end[0, j] *= 0.5                        # below its latest leaf
    bad = dataclasses.replace(res, subtask_end=end)
    with pytest.raises(VerifyError) as ei:
        verify_batch_result(batch, bad)
    assert ei.value.kinds == {"join"}


def test_ir_lint_rejects_a_join_row_with_work_or_no_reader():
    batch, _ = join_fixture()
    j, s = int(batch.n_sub[0]), batch.max_subtasks
    dur = np.array(batch.duration)
    dur[0, j] = 1.0
    with pytest.raises(IRLintError, match="n_rows"):
        lint_batch(dataclasses.replace(batch, duration=dur))
    # nobody reads the join row: its slot becomes padding
    pred, lat, volbw = (np.array(x) for x in
                        (batch.pred, batch.pred_lat, batch.pred_volbw))
    at = pred == j
    pred[at], lat[at], volbw[at] = s, -np.inf, -np.inf
    with pytest.raises(IRLintError, match="n_rows"):
        lint_batch(dataclasses.replace(batch, pred=pred, pred_lat=lat,
                                       pred_volbw=volbw))


# ---------------------------------------------------------------------------
# IR linter: lowered-array contract violations
# ---------------------------------------------------------------------------

def test_lint_ir_accepts_every_lowered_container():
    m = dell_poweredge_1950()
    g = generate_app(SynthParams(n_tasks=(6, 9)), seed=3)
    sch = get_scheduler("engine")(g, m)
    sa = lowering.lower_scenario(g, m, sch)
    for obj in (lowering.machine_arrays(m), lowering.graph_arrays(g), sa,
                lowering.batch_scenarios([sa]),
                lowering.population_arrays(g, m)):
        lint_ir(obj)
    with pytest.raises(IRLintError, match="no IR lint"):
        lint_ir(object())


def test_ir_lint_oob_gather_index_in_batch():
    batch, _ = batch_fixture()
    s = batch.max_subtasks
    pred = np.array(batch.pred)
    pred[0, 0, 0] = s + 3               # past the sentinel slot
    with pytest.raises(IRLintError, match="gather-bounds"):
        lint_batch(dataclasses.replace(batch, pred=pred))


def test_ir_lint_nonmonotone_csr():
    g = two_task_graph()
    ga = lowering.graph_arrays(g)
    ptr = np.array(ga.pred_ptr)
    ptr[0] = 1
    with pytest.raises(IRLintError, match="pred_ptr"):
        lint_graph_arrays(dataclasses.replace(ga, pred_ptr=ptr))


def test_ir_lint_cycle_detected():
    # finalize() rejects cyclic AppGraphs, so corrupt the lowered CSR
    # directly: 0 -> 1 plus a smuggled 1 -> 0 back edge
    ga = lowering.graph_arrays(two_task_graph())
    it, fl = ga.pred_ptr.dtype, ga.pred_vol.dtype
    bad = dataclasses.replace(
        ga,
        pred_ptr=np.array([0, 1, 2], it), pred_sid=np.array([1, 0], it),
        pred_vol=np.array([1.0, 1.0], fl),
        succ_ptr=np.array([0, 1, 2], it), succ_sid=np.array([1, 0], it),
        succ_vol=np.array([1.0, 1.0], fl))
    with pytest.raises(IRLintError, match="cycle"):
        lint_graph_arrays(bad)


def test_ir_lint_corrupt_wave_index():
    batch, _ = batch_fixture()
    wave = np.zeros_like(np.array(batch.wave))      # flattens the DAG
    with pytest.raises(IRLintError, match="wave"):
        lint_batch(dataclasses.replace(batch, wave=wave))


def test_ir_lint_population_topo_violation():
    m = dell_poweredge_1950()
    pa = lowering.population_arrays(two_task_graph(), m)
    s = pa.n_subtasks
    pp = np.array(pa.pred_pos)
    i, k = map(int, np.argwhere(pp < s)[0])
    pp[i, k] = i                        # producer at its consumer's slot
    with pytest.raises(IRLintError, match="pred_pos"):
        lint_population_arrays(dataclasses.replace(pa, pred_pos=pp))
    pp[i, k] = s + 2                    # and out past the sentinel
    with pytest.raises(IRLintError, match="gather-bounds"):
        lint_population_arrays(dataclasses.replace(pa, pred_pos=pp))


def test_kernel_wrapper_rejects_oob_gather():
    from repro.kernels import ops
    pred = np.full((1, 2, 1), 3, dtype=np.int32)    # S=2: sentinel is 2
    zeros3, zeros2 = np.zeros((1, 2, 1)), np.zeros((1, 2))
    with pytest.raises(IRLintError, match="gather-bounds"):
        ops.sim_relax_pop(pred, zeros3, zeros3, np.ones((1, 2)), zeros2,
                          n_steps=1)


# ---------------------------------------------------------------------------
# AST repo lint
# ---------------------------------------------------------------------------

def test_lint_flags_deprecated_import_and_pragma_suppresses():
    src = "from repro.core.engine import comm_matrices\n"
    out = lint_source(src, "src/repro/foo.py")
    assert [v.rule for v in out] == ["deprecated-api"]
    ok = src.rstrip() + "  # lint: deprecated-ok\n"
    assert lint_source(ok, "src/repro/foo.py") == []
    # the defining module may keep its own alias
    assert lint_source(src, "src/repro/core/engine.py") == []


def test_lint_flags_deprecated_attribute_use():
    src = ("from repro.core import engine\n"
           "from repro.kernels import sched_ref\n"
           "M = engine.comm_matrices(g, m)\n"
           "D = sched_ref.drain_matrix(batch)\n")
    out = lint_source(src, "benchmarks/bench.py")
    assert [v.rule for v in out] == ["deprecated-api", "deprecated-api"]
    assert out[0].line == 3 and out[1].line == 4


def test_lint_flags_host_rng_only_inside_device_scope():
    body = ("import jax\n"
            "import numpy as np\n"
            "{dec}def step(x):\n"
            "    return x + np.random.rand()\n")
    assert lint_source(body.format(dec=""), "m.py") == []
    out = lint_source(body.format(dec="@jax.jit\n"), "m.py")
    assert [v.rule for v in out] == ["host-sync"]


def test_lint_flags_item_in_jit_entry_passed_by_name():
    src = textwrap.dedent("""
        import jax
        def kernel(x):
            return x.item()
        run = jax.jit(kernel)
    """)
    out = lint_source(src, "m.py")
    assert [v.rule for v in out] == ["host-sync"]


def test_lint_flags_float_of_traced_param():
    src = textwrap.dedent("""
        import jax
        @jax.jit
        def f(x):
            y = float(x)
            z = float(3.0)
            return y + z
    """)
    out = lint_source(src, "m.py")
    assert [v.rule for v in out] == ["host-sync"]   # only float(x)


def test_lint_flags_frozen_mutation_outside_allowlist():
    src = "object.__setattr__(obj, 'cache', 1)\n"
    out = lint_source(src, "src/repro/search/ga.py")
    assert [v.rule for v in out] == ["frozen-mutation"]
    assert lint_source(src, "src/repro/core/lowering.py") == []


def test_repo_is_lint_clean():
    repo = Path(__file__).resolve().parents[1]
    from repro.analysis.lint import lint_paths
    bad = lint_paths([repo / "src" / "repro", repo / "benchmarks",
                      repo / "tests"])
    assert bad == [], "\n".join(str(v) for v in bad)


# ---------------------------------------------------------------------------
# verify= integration: registry, every scheduler, online cluster
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SCHEDULERS))
def test_every_registered_scheduler_verifies(name):
    from repro.search.ga import GAParams
    m = dell_poweredge_1950()
    g = generate_app(SynthParams(n_tasks=(8, 12)), seed=7)
    kwargs = ({"params": GAParams(pop_size=6, generations=3,
                                  refine_rounds=0)}
              if name == "ga" else {})
    sch = get_scheduler(name, verify=True)(g, m, **kwargs)
    assert sch.placements


def test_verifier_passes_on_larger_machines():
    g = generate_app(SynthParams(n_tasks=(15, 20)), seed=5)
    for m in (hp_bl260c(), cluster_of_multicores(n_blades=32)):
        sch = get_scheduler("engine", verify=True)(g, m)
        assert len(sch.placements) == g.n_subtasks


def test_registry_wrapper_rejects_broken_scheduler():
    def drops_first(graph, machine, **kw):
        sch = get_scheduler("engine")(graph, machine, **kw)
        return rebuilt(sch, skip=(0,))

    register_scheduler("_test_bad", drops_first, doc="drops sid 0",
                       overwrite=True)
    try:
        m = dell_poweredge_1950()
        g = generate_app(SynthParams(n_tasks=(6, 9)), seed=1)
        assert get_scheduler("_test_bad")(g, m)     # unverified: passes
        with pytest.raises(VerifyError) as ei:
            get_scheduler("_test_bad", verify=True)(g, m)
        assert "namespace" in ei.value.kinds
    finally:
        SCHEDULERS.pop("_test_bad", None)


def test_cluster_verify_on_admissions_and_corruption():
    from repro.online import ArrivalParams, OnlineAMTHA, generate_workload
    eng = OnlineAMTHA(dell_poweredge_1950(), verify=True)
    for a in generate_workload(ArrivalParams(), n_apps=3, seed=4):
        eng.admit(a)                    # verify_cluster after each commit
    assert verify_cluster(eng.state, collect=True) == []
    sid = max(eng.state.schedule.placements)
    eng.state.schedule.remove(sid)      # an app lost an interval
    with pytest.raises(VerifyError) as ei:
        verify_cluster(eng.state)
    assert "namespace" in ei.value.kinds
