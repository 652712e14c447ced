"""Montage workflows (``core/workflows.py``) and the bounded predecessor
layout they force (``core/lowering.PredLayout``).

A 4 x 4 Montage's ``mConcatFit`` joins 33 fits, more than the 31
predecessor columns a row may hold, so it is read through join rows.
The layout must be exact: every test here compares it with the
unbounded layout (``ROW_COLUMNS`` raised past every fan-in) or with the
seed event simulator, for ends, faults and the device search."""

import numpy as np
import pytest

from repro import obs
from repro.analysis import lint_batch, verify_batch_result, verify_schedule
from repro.core import (get_scheduler, hp_bl260c, lowering,
                        paper_suite_64core, simulate_suite)
from repro.core.simulator import simulate
from repro.core.workflows import montage, montage_pairs
from repro.faults import FaultScript, core_fail, link_degrade


@pytest.mark.parametrize("g", [4, 14, 16])
def test_montage_shape_and_fan_ins(g):
    wf = montage(g, seed=200)
    n, pairs = g * g, 2 * g * (g - 1) + (g - 1) ** 2
    assert len(montage_pairs(g)) == pairs
    # mProjectPP, mDiffFit, mBackground per image or pair, then
    # mConcatFit, mBgModel, mImgtbl, mAdd, mShrink, mJPEG
    assert wf.n_subtasks == 2 * n + pairs + 6
    fan_in = [len(p) for p in wf.preds]
    concat = n + pairs
    imgtbl, add = concat + 2 + n, concat + 3 + n
    assert fan_in[concat] == pairs
    assert fan_in[imgtbl] == n
    assert fan_in[add] == n + 1
    assert all(f == 0 for f in fan_in[:n])              # mProjectPP
    assert all(f == 2 for f in fan_in[n:concat])        # mDiffFit
    assert max(fan_in) == pairs
    if g == 16:
        assert (wf.n_subtasks, pairs) == (1223, 705)


def test_montage_is_seeded():
    a, b = montage(4, 7), montage(4, 7)
    assert [s.times for s in a.subtasks] == [s.times for s in b.subtasks]
    assert [s.times for s in a.subtasks] != \
        [s.times for s in montage(4, 8).subtasks]
    with pytest.raises(ValueError):
        montage(1, 0)


@pytest.fixture
def unbounded(monkeypatch):
    """Lower graphs built inside the fixture's scope with no join rows."""
    def lower(fn):
        with monkeypatch.context() as mp:
            mp.setattr(lowering, "ROW_COLUMNS", 10 ** 9)
            return fn()
    return lower


def _mapped(seed=1):
    m = hp_bl260c()
    wf = montage(4, seed)
    return m, wf, get_scheduler("engine")(wf, m)


def test_layout_of_a_wide_join():
    _, wf, _ = _mapped()
    lay = lowering.pred_layout(lowering.graph_arrays(wf))
    concat = 16 + 33
    assert lay.n_joins == 2 and lay.width == lowering.ROW_COLUMNS - 1
    assert lay.join_consumer.tolist() == [concat, concat]
    assert lay.join_row.tolist() == [concat, concat]
    # every edge sits once, in a row of at most 31 columns
    slots = set(zip(lay.edge_row.tolist(), lay.edge_col.tolist()))
    slots |= set(zip(lay.join_row.tolist(), lay.join_col.tolist()))
    assert len(slots) == len(lay.edge_row) + lay.n_joins
    assert max(c for _, c in slots) < lowering.ROW_COLUMNS - 1


@pytest.mark.parametrize("backend", ["pallas", "numpy"])
def test_join_rows_match_the_event_simulator(backend):
    m, wf, sch = _mapped()
    ref = simulate(wf, m, sch, contention=False)
    res = simulate_suite([wf], m, [sch], backend=backend, verify=True)
    n = wf.n_subtasks
    want = np.array([ref.subtask_end[s] for s in range(n)])
    np.testing.assert_allclose(res.subtask_end[0, :n], want, rtol=1e-5)
    assert res.t_exec[0] == pytest.approx(ref.t_exec, rel=1e-5)
    assert res.n_sub[0] == n


@pytest.mark.parametrize("backend", ["pallas", "numpy"])
def test_bounded_layout_is_bit_identical_under_jitter(backend, unbounded):
    m, _, sch = _mapped()
    seeds = [3, 11, 2**31 + 5]
    wide = [montage(4, 1) for _ in seeds]
    flat = unbounded(lambda: [montage(4, 1) for _ in seeds])
    got = simulate_suite(wide, m, [sch] * 3, jitter=0.05, seeds=seeds,
                         backend=backend)
    want = unbounded(lambda: simulate_suite(flat, m, [sch] * 3, jitter=0.05,
                                            seeds=seeds, backend=backend))
    n = wide[0].n_subtasks
    assert got.subtask_end.shape[1] == n + 2
    assert want.subtask_end.shape[1] == n
    assert np.array_equal(got.subtask_end[:, :n], want.subtask_end)
    assert np.array_equal(got.t_exec, want.t_exec)


def test_paper_class_lowers_exactly_as_unbounded(unbounded):
    m = hp_bl260c()
    apps = paper_suite_64core(n_apps=3)
    scheds = [get_scheduler("engine")(a, m) for a in apps]
    obs.reset()
    batch = lowering.batch_scenarios([lowering.lower_scenario(a, m, s)
                                      for a, s in zip(apps, scheds)])
    assert obs.snapshot()["counters"]["lower.join_rows"] == 0
    flat = unbounded(lambda: lowering.batch_scenarios(
        [lowering.lower_scenario(a, m, s)
         for a, s in zip(paper_suite_64core(n_apps=3), scheds)]))
    assert batch.max_preds == flat.max_preds <= lowering.ROW_COLUMNS - 1
    assert np.array_equal(batch.n_rows, batch.n_sub)
    for f in ("n_sub", "duration", "release", "prev", "pred", "pred_lat",
              "pred_volbw", "wave", "t_est"):
        assert np.array_equal(getattr(batch, f), getattr(flat, f)), f
    assert batch.depth == flat.depth
    a = simulate_suite(apps, m, scheds, jitter=0.01, seeds=[1, 2, 3])
    b = unbounded(lambda: simulate_suite(paper_suite_64core(n_apps=3), m,
                                         scheds, jitter=0.01,
                                         seeds=[1, 2, 3]))
    assert np.array_equal(a.t_exec, b.t_exec)


def test_degraded_edge_into_a_wide_join(unbounded):
    m, wf, sch = _mapped()
    concat = 16 + 33
    g = lowering.graph_arrays(wf)
    lo, hi = g.pred_ptr[concat], g.pred_ptr[concat + 1]
    cc = sch.placements[concat].core
    # every fit reaches mConcatFit through a join row: slow the link of
    # the last one sent from another core until it decides the join
    src = next(int(p) for p in g.pred_sid[lo:hi][::-1]
               if sch.placements[int(p)].core != cc)
    script = FaultScript((link_degrade(0.0, sch.placements[src].core, cc,
                                       1e5),
                          core_fail(sch.makespan() * 0.99, 0)))
    got = simulate_suite([wf], m, [sch], faults=script, verify=True)
    want = unbounded(lambda: simulate_suite([montage(4, 1)], m, [sch],
                                            faults=script))
    n = wf.n_subtasks
    assert np.array_equal(got.subtask_end[:, :n], want.subtask_end)
    ref = simulate(wf, m, sch, contention=False, faults=script)
    end = np.array([ref.subtask_end[s] for s in range(n)])
    fin = np.isfinite(end)
    assert set(np.flatnonzero(~np.isfinite(got.subtask_end[0, :n]))) == \
        set(ref.stranded)
    np.testing.assert_allclose(got.subtask_end[0, :n][fin], end[fin],
                               rtol=1e-9)
    assert got.subtask_end[0, concat] > sch.placements[concat].end


def test_suite_call_counts_join_rows_slots_and_edges():
    m, wf, sch = _mapped()
    obs.reset()
    simulate_suite([wf, wf], m, [sch, sch], backend="numpy")
    snap = obs.snapshot()
    root = snap["spans"][-1]
    assert root["name"] == "suite.call"
    c = root["counts"]
    n, e = wf.n_subtasks, len(wf.edges)
    in_order = n - len({p.core for p in sch.placements.values()})
    assert c["lower.join_rows"] == 2 * 2
    assert c["lower.edges"] == 2 * (e + in_order)
    assert c["lower.edge_slots"] == 2 * (n + 2) * lowering.ROW_COLUMNS
    assert any(s["name"] == "lower.join" and s["root"] == root["id"]
               for s in snap["spans"])


def test_lint_and_verifier_accept_join_rows():
    m, wf, sch = _mapped()
    batch = lowering.batch_scenarios([lowering.lower_scenario(wf, m, sch)])
    lint_batch(batch)
    res = simulate_suite([wf], m, [sch])
    assert verify_batch_result(batch, res) == []
    n = wf.n_subtasks
    lag = batch.pred_lat + batch.pred_volbw
    for j in (n, n + 1):
        real = batch.pred[0, j] < batch.max_subtasks
        ends = res.subtask_end[0][batch.pred[0, j][real]]
        assert res.subtask_end[0, j] == (ends + lag[0, j][real]).max()


@pytest.mark.parametrize("method", ["scan", "kernel"])
def test_device_fitness_with_join_rows_is_the_unbounded(method, unbounded):
    import jax.numpy as jnp

    from repro.search import device_inputs, population_fitness_device

    m, wf, _ = _mapped()
    pop = np.random.default_rng(0).integers(
        0, m.n_cores, (6, len(wf.tasks))).astype(np.int32)
    pa = lowering.population_arrays(wf, m)
    assert pa.n_rows == pa.n_subtasks + 2
    assert (pa.topo_sid == -1).sum() == 2
    fit = population_fitness_device(device_inputs(wf, m), jnp.asarray(pop),
                                    method=method)
    flat = unbounded(lambda: device_inputs(montage(4, 1), m))
    want = population_fitness_device(flat, jnp.asarray(pop), method=method)
    assert np.array_equal(np.asarray(fit), np.asarray(want))


def test_device_search_maps_a_montage():
    from repro.search import GAParams, ga_schedule

    m, wf, sch = _mapped()
    par = GAParams(pop_size=8, generations=3, refine_rounds=1,
                   refine_moves=8, device=True)
    got = ga_schedule(wf, m, seed=5, params=par)
    assert verify_schedule(got, wf, m) == []
    assert got.makespan() <= sch.makespan() + 1e-9
