"""Simulator equivalence: the lowered event loop must reproduce the
seed ``simulate()`` bit-for-bit (contention + jitter + releases), and
the batched relaxation must reproduce the analytic event semantics —
single vs batched, NumPy CSR vs wave-scheduled vs the sparse Pallas
kernel are all swept against each other."""

import numpy as np
import pytest

from repro.core import (AppGraph, Schedule, SimResult, SynthParams,
                        batch_scenarios, dell_poweredge_1950,
                        engine_schedule, generate_app, heterogeneous_cluster,
                        hp_bl260c, lower_scenario, paper_suite_8core,
                        repeat_batch, simulate, simulate_arrays,
                        simulate_batch, simulate_scenario, simulate_suite)
from repro.core.lowering import relax_operands
from repro.core.machine import CommLevel, MachineModel
from repro.core.sim_engine import relax_batch_np, relax_wave_np
from repro.online import ArrivalParams, generate_workload, replay_fifo


def _scenarios(machine, params, n, seed0=0):
    apps = [generate_app(params, seed=seed0 + i) for i in range(n)]
    schedules = [engine_schedule(g, machine) for g in apps]
    return apps, schedules


# ---------------------------------------------------------------------------
# exact event-loop equivalence (bit for bit)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("contention", [False, True])
@pytest.mark.parametrize("jitter", [0.0, 0.05])
def test_array_event_loop_bit_for_bit_8core_suite(contention, jitter):
    m = dell_poweredge_1950()
    for i, g in enumerate(paper_suite_8core(n_apps=4)):
        s = engine_schedule(g, m)
        ref = simulate(g, m, s, contention=contention, jitter=jitter, seed=i)
        got = simulate_scenario(g, m, s, contention=contention,
                                jitter=jitter, seed=i)
        assert ref.t_exec == got.t_exec
        assert ref.subtask_end == got.subtask_end


def test_array_event_loop_bit_for_bit_64core():
    m = hp_bl260c(n_blades=2)
    g = generate_app(SynthParams(n_tasks=(40, 60)), seed=7)
    s = engine_schedule(g, m)
    ref = simulate(g, m, s, contention=True, jitter=0.02, seed=3)
    got = simulate_scenario(g, m, s, contention=True, jitter=0.02, seed=3)
    assert ref.t_exec == got.t_exec
    assert ref.subtask_end == got.subtask_end


def test_release_tie_order_matches_seed_dict_order():
    """Tied release instants drain in the dict's insertion order (the
    seed iterates ``releases.items()``); under jitter, that order picks
    which subtask draws first from the RNG, so replaying releases in
    sid order would diverge — regression for the lowered loop."""
    m = dell_poweredge_1950()
    g = generate_app(SynthParams(n_tasks=(15, 25)), seed=21)
    s = engine_schedule(g, m)
    g.finalize()
    roots = [sid for sid in range(g.n_subtasks) if not g.preds[sid]]
    releases = {sid: 5.0 for sid in reversed(roots)}     # tied, reversed
    ref = simulate(g, m, s, contention=True, jitter=0.05, seed=0,
                   releases=releases)
    got = simulate_scenario(g, m, s, contention=True, jitter=0.05, seed=0,
                            releases=releases)
    assert ref.t_exec == got.t_exec
    assert ref.subtask_end == got.subtask_end


def test_release_for_unknown_subtask_raises():
    """A stale / pre-merge sid in the releases dict is a namespace bug;
    the lowering surfaces it instead of silently running the subtask
    from t=0 (the seed loop fails on the same input with IndexError)."""
    m = dell_poweredge_1950()
    g = generate_app(SynthParams(n_tasks=(3, 5)), seed=1)
    s = engine_schedule(g, m)
    with pytest.raises(ValueError, match="unknown subtask"):
        lower_scenario(g, m, s, releases={g.n_subtasks + 500: 1.0})
    with pytest.raises(ValueError, match="unknown subtask"):
        lower_scenario(g, m, s, releases={-1: 1.0})


def test_array_event_loop_bit_for_bit_with_releases():
    """The online injection hook: a multiprogrammed timeline with
    per-app arrival releases simulates identically on both loops."""
    m = dell_poweredge_1950()
    state = replay_fifo(m, generate_workload(ArrivalParams(rate=0.05), 5,
                                             seed=11))
    merged = state.merged_graph()
    rel = state.releases()
    ref = simulate(merged, m, state.schedule, contention=True, jitter=0.01,
                   seed=2, releases=rel)
    got = simulate_scenario(merged, m, state.schedule, contention=True,
                            jitter=0.01, seed=2, releases=rel)
    assert ref.t_exec == got.t_exec
    assert ref.subtask_end == got.subtask_end


# ---------------------------------------------------------------------------
# batched relaxation vs single-scenario analytic event loop
# ---------------------------------------------------------------------------

def test_batched_matches_single_deterministic():
    m = dell_poweredge_1950()
    apps, schedules = _scenarios(m, SynthParams(n_tasks=(15, 25)), 6)
    res = simulate_suite(apps, m, schedules, jitter=0.0)
    for i, (g, s) in enumerate(zip(apps, schedules)):
        ref = simulate(g, m, s, contention=False, jitter=0.0)
        assert np.isclose(res.t_exec[i], ref.t_exec, rtol=1e-9, atol=1e-9)
        ends = res.subtask_end[i, :g.n_subtasks]
        want = np.array([ref.subtask_end[sid] for sid in range(g.n_subtasks)])
        np.testing.assert_allclose(ends, want, rtol=1e-9, atol=1e-9)
        assert np.isclose(res.t_est[i], s.makespan())


def test_batched_mixes_machines_and_graph_sizes():
    """One batch may hold scenarios of different machines (8-core and
    heterogeneous) and very different graph sizes — the IR reduces
    everything to per-edge lags, so core counts never pad."""
    m8, mh = dell_poweredge_1950(), heterogeneous_cluster()
    scens, refs = [], []
    for i, (m, p) in enumerate([(m8, SynthParams(n_tasks=(15, 25))),
                                (mh, SynthParams(n_tasks=(3, 5), n_types=2)),
                                (m8, SynthParams(n_tasks=(2, 3)))]):
        g = generate_app(p, seed=i)
        s = engine_schedule(g, m)
        scens.append(lower_scenario(g, m, s))
        refs.append(simulate(g, m, s, contention=False, jitter=0.0))
    res = simulate_batch(scens)
    np.testing.assert_allclose(res.t_exec, [r.t_exec for r in refs],
                               rtol=1e-9, atol=1e-9)


def test_batched_respects_releases():
    m = dell_poweredge_1950()
    state = replay_fifo(m, generate_workload(ArrivalParams(rate=0.05), 4,
                                             seed=5))
    merged = state.merged_graph()
    rel = state.releases()
    ref = simulate(merged, m, state.schedule, contention=False, jitter=0.0,
                   releases=rel)
    res = simulate_suite([merged], m, [state.schedule], releases=[rel])
    assert np.isclose(res.t_exec[0], ref.t_exec, rtol=1e-9, atol=1e-9)


def test_batched_jitter_statistically_matches_event_loop():
    """Jitter draws happen in a different order (sid vs event), so only
    the distribution matches: suite means agree to a couple percent."""
    m = dell_poweredge_1950()
    g = generate_app(SynthParams(n_tasks=(15, 25)), seed=3)
    s = engine_schedule(g, m)
    n = 60
    ref = np.mean([simulate(g, m, s, contention=False, jitter=0.08,
                            seed=i).t_exec for i in range(n)])
    batch = repeat_batch(batch_scenarios([lower_scenario(g, m, s)]), n)
    got = simulate_batch(batch, jitter=0.08, seeds=range(1000, 1000 + n))
    assert abs(got.t_exec.mean() - ref) / ref < 0.02


def test_wave_and_jacobi_relaxations_agree_exactly():
    m = dell_poweredge_1950()
    apps, schedules = _scenarios(m, SynthParams(n_tasks=(10, 15)), 5)
    batch = batch_scenarios([lower_scenario(g, m, s)
                             for g, s in zip(apps, schedules)])
    assert np.array_equal(relax_wave_np(batch), relax_batch_np(batch))


def test_repeat_batch_tiles_scenarios():
    m = dell_poweredge_1950()
    apps, schedules = _scenarios(m, SynthParams(n_tasks=(5, 8)), 2)
    batch = batch_scenarios([lower_scenario(g, m, s)
                             for g, s in zip(apps, schedules)])
    tiled = repeat_batch(batch, 3)
    assert tiled.n_scenarios == 6
    res = simulate_batch(tiled)
    np.testing.assert_array_equal(res.t_exec[:2], res.t_exec[2:4])
    np.testing.assert_array_equal(res.t_exec[:2], res.t_exec[4:])


def test_relax_operands_append_the_in_order_column():
    m = dell_poweredge_1950()
    apps, schedules = _scenarios(m, SynthParams(n_tasks=(10, 15)), 4)
    batch = batch_scenarios([lower_scenario(g, m, s)
                             for g, s in zip(apps, schedules)])
    b, s, p = batch.n_scenarios, batch.max_subtasks, batch.max_preds
    pred, lat, volbw = relax_operands(batch)
    assert relax_operands(batch)[0] is pred          # cached on the batch
    for x in (pred, lat, volbw):
        assert x.shape == (b, s, p + 1)
    assert pred.dtype == np.int64
    assert lat.dtype == volbw.dtype == np.float64
    np.testing.assert_array_equal(pred[:, :, :p], batch.pred)
    np.testing.assert_array_equal(lat[:, :, :p], batch.pred_lat)
    np.testing.assert_array_equal(volbw[:, :, :p], batch.pred_volbw)
    np.testing.assert_array_equal(pred[:, :, p], batch.prev)
    has_prev = batch.prev < s
    assert has_prev.any() and not has_prev.all()
    for x in (lat, volbw):
        assert np.all(x[:, :, p][has_prev] == 0.0)
        assert np.all(x[:, :, p][~has_prev] == -np.inf)
    assert np.array_equal(relax_wave_np(batch), relax_batch_np(batch))


# ---------------------------------------------------------------------------
# sim_relax_pop Pallas kernel vs the CSR wave
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_apps,n_tasks", [(2, (3, 5)), (3, (5, 10))])
def test_simulate_batch_pallas_backend_smoke(n_apps, n_tasks):
    m = dell_poweredge_1950()
    apps, schedules = _scenarios(m, SynthParams(n_tasks=n_tasks), n_apps)
    scens = [lower_scenario(g, m, s) for g, s in zip(apps, schedules)]
    ref = simulate_batch(scens, backend="numpy")
    got = simulate_batch(scens, backend="pallas")
    np.testing.assert_allclose(got.t_exec, ref.t_exec, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(got.subtask_end, ref.subtask_end, rtol=1e-5,
                               atol=1e-3)
    with pytest.raises(ValueError):
        simulate_batch(scens, backend="cuda")


# ---------------------------------------------------------------------------
# degenerate scenarios (the dif_rel regression)
# ---------------------------------------------------------------------------

def test_dif_rel_zero_t_exec_returns_zero():
    assert SimResult(0.0, {}).dif_rel(0.0) == 0.0
    assert SimResult(0.0, {}).dif_rel(5.0) == 0.0
    assert SimResult(10.0, {}).dif_rel(5.0) == pytest.approx(50.0)


def test_empty_graph_simulates_to_zero_everywhere():
    m = dell_poweredge_1950()
    g = AppGraph(n_types=1)
    g.finalize()
    sched = Schedule(m.n_cores)
    for sim in (simulate, simulate_scenario):
        r = sim(g, m, sched)
        assert r.t_exec == 0.0
        assert r.dif_rel(0.0) == 0.0
    res = simulate_suite([g], m, [sched])
    assert res.t_exec[0] == 0.0
    assert res.dif_rel()[0] == 0.0


# ---------------------------------------------------------------------------
# lowering dedup: one source of truth
# ---------------------------------------------------------------------------

def test_engine_comm_matrices_is_deprecated_lowering_alias():
    from repro.core.engine import comm_matrices as engine_cm  # lint: deprecated-ok
    from repro.core.lowering import comm_matrices as lowering_cm
    m = dell_poweredge_1950()
    with pytest.warns(DeprecationWarning, match="lowering.comm_matrices"):
        lat_e, bw_e = engine_cm(m)
    lat_l, bw_l = lowering_cm(m)
    assert lat_e is lat_l and bw_e is bw_l      # shared cache, no copy
    lvl = m.comm_level(0, 7)
    assert lat_l[0, 7] == lvl.latency and bw_l[0, 7] == lvl.bandwidth
    assert lat_l[3, 3] == 0.0 and np.isinf(bw_l[3, 3])


def test_sched_ref_drain_matrix_is_deprecated_lowering_alias():
    from repro.core.lowering import drain_matrix as lowering_dm
    from repro.kernels.sched_ref import drain_matrix as kernel_dm  # lint: deprecated-ok
    m = heterogeneous_cluster(n_fast=2, n_slow=2)
    gs = [generate_app(SynthParams(n_types=2), seed=i) for i in range(2)]
    with pytest.warns(DeprecationWarning, match="lowering.drain_matrix"):
        deprecated = kernel_dm(gs, m)
    np.testing.assert_array_equal(deprecated, lowering_dm(gs, m))


def test_sched_score_drain_matrix_is_the_lowering_function():
    # the kernel-facing re-export migrated off the deprecated alias
    from repro.core.lowering import drain_matrix as lowering_dm
    from repro.kernels.sched_score import drain_matrix as kernel_dm
    assert kernel_dm is lowering_dm


# ---------------------------------------------------------------------------
# hypothesis sweep over machines / graphs / releases
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                          # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @st.composite
    def machines(draw):
        n_types = draw(st.integers(1, 3))
        cores, locs = [], []
        for grp in range(draw(st.integers(1, 3))):
            for c in range(draw(st.integers(1, 4))):
                locs.append((grp, c))
                cores.append(draw(st.integers(0, n_types - 1)))
        for t in range(n_types):
            if t not in cores:
                cores[t % len(cores)] = t
        levels = [CommLevel("net", 1e-5, draw(st.floats(1e6, 1e9))),
                  CommLevel("ram", 1e-7, draw(st.floats(1e9, 1e11)))]
        return MachineModel("hyp", cores, locs, levels, n_types=n_types)

    @st.composite
    def scenarios(draw):
        m = draw(machines())
        params = SynthParams(
            n_tasks=(2, draw(st.integers(3, 10))),
            subtasks_per_task=(1, draw(st.integers(2, 6))),
            task_size_s=(0.5, draw(st.floats(1.0, 60.0))),
            comm_volume=(10.0, draw(st.floats(100.0, 1e6))),
            comm_probability=(0.05, draw(st.floats(0.1, 0.9))),
            n_types=m.n_types)
        g = generate_app(params, seed=draw(st.integers(0, 2**31 - 1)))
        jitter = draw(st.sampled_from([0.0, 0.05]))
        n_rel = draw(st.integers(0, 3))
        # arbitrary insertion order — the lowered loop must replay
        # releases in dict order, not sid order (ties break by it)
        releases = {draw(st.integers(0, g.n_subtasks - 1)):
                    draw(st.floats(0.0, 50.0)) for _ in range(n_rel)}
        return m, g, jitter, releases

    @given(scenarios(), st.integers(0, 1000))
    @settings(max_examples=25, deadline=None)
    def test_event_loop_equivalence_property(scenario, seed):
        m, g, jitter, releases = scenario
        s = engine_schedule(g, m)
        for contention in (False, True):
            ref = simulate(g, m, s, contention=contention, jitter=jitter,
                           seed=seed, releases=dict(releases))
            got = simulate_scenario(g, m, s, contention=contention,
                                    jitter=jitter, seed=seed,
                                    releases=dict(releases))
            assert ref.t_exec == got.t_exec
            assert ref.subtask_end == got.subtask_end

    @given(scenarios())
    @settings(max_examples=25, deadline=None)
    def test_batched_equivalence_property(scenario):
        m, g, _, releases = scenario
        s = engine_schedule(g, m)
        ref = simulate(g, m, s, contention=False, jitter=0.0,
                       releases=dict(releases))
        res = simulate_suite([g], m, [s], releases=[dict(releases)])
        assert np.isclose(res.t_exec[0], ref.t_exec, rtol=1e-9, atol=1e-9)
