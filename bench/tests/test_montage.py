"""The Montage cell: found from added files, its traffic the program's own
workflows, its reference the plain relaxation, and its comparison able
to see a program that drops part of a wide join.

The runs use a tiny cell (``data/tiny-montage.json``: the tiny 8-core
machine, 4 x 4 and 5 x 5 grids, whose ``mConcatFit`` joins 33 and 56
fits and so gets join rows) with the committed mix's jitter, backend
and limits."""

import dataclasses
import json
import shutil

import numpy as np
import pytest

from bench import discover, program, reference, workflows
from bench.synth import AppData

ROOT = discover.ROOT
CELL = "suite-montage"
TINY = json.loads((discover.BENCH / "tests" / "data" / "tiny-montage.json")
                  .read_text())


def _traffic() -> dict:
    return json.loads((discover.BENCH / "traffic"
                       / "suite-montage-jitter16.json").read_text())


@pytest.fixture
def tiny_montage():
    return discover.Cell(
        name="tiny-montage", chips=1, config=TINY,
        traffic=dict(_traffic(), graph_seeds=[1, 2], draws=3,
                     schedules="tests/data/tiny-montage-schedules.json"),
        kind=discover.kind("suite_workflow"), end_to_end=[], per_layer=[],
        readers={})


def test_tiny_montage_cell_is_found_by_adding_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "bench/configs/tiny-montage-8.json").write_text(json.dumps(TINY))
    traffic = dict(_traffic(), graph_seeds=[1, 2], draws=3,
                   schedules="tests/data/tiny-montage-schedules.json")
    (root / "bench/traffic/tiny-montage.json").write_text(json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-montage-8", "source": "test",
                             "file": "bench/configs/tiny-montage-8.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-montage",
                               "config": "tiny-montage-8",
                               "traffic": "tiny-montage", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-montage")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = discover.resolve("tiny-montage", root=root,
                            bench_dir=root / "bench")
    assert cell.kind.__name__ == "bench.kinds.suite_workflow"
    assert cell.config["family"] == "montage"
    assert {m["name"] for m in cell.end_to_end} == {"suite_scenarios_per_s",
                                                    "setup_s"}
    assert set(cell.readers) == {"relax_roofline.montage", "join_ms.montage",
                                 "slots_per_edge.montage",
                                 "idle_share.montage", "lower_ms.montage",
                                 "lower_scenario_ms.montage",
                                 "batch_ms.montage"}


@pytest.mark.parametrize("seed", [200, 201, 202, 203])
def test_generator_names_the_programs_workflow(seed):
    program.import_path()
    from repro.core.workflows import montage

    cfg = discover.resolve(CELL).config
    app = workflows.from_config(cfg, seed)
    g = montage(workflows.grid_side(cfg, seed), seed)
    assert workflows.grid_side(cfg, seed) == 14 + seed % 3
    assert app.times == [st.times for st in g.subtasks]
    assert app.edges == [(e.src, e.dst, e.volume) for e in g.edges]
    assert app.tasks == [g.tasks[t] for t in sorted(g.tasks)]


def test_committed_schedules_are_schedules_of_the_mixs_workflows():
    cell = discover.resolve(CELL)
    tr = cell.traffic
    data = json.loads((discover.BENCH / tr["schedules"]).read_text())
    m = reference.Machine(cell.config["machine"])
    assert data["config"] == cell.config["name"]
    assert [a["graph_seed"] for a in data["apps"]] == tr["graph_seeds"]
    for row in data["apps"]:
        app = workflows.from_config(cell.config, row["graph_seed"])
        core, start = np.asarray(row["core"]), np.asarray(row["start"])
        end = start + np.array([m.exec_time(app, s, int(core[s]))
                                for s in range(app.n_subtasks)])
        worst, bad = reference.violations(app, m, core, start, end, 0.0)
        assert bad == 0 and worst <= 1e-9
        assert reference.overlap(core, start, end) <= 1e-9


def _small_apps():
    from bench.synth import AppParams, generate_app
    yield workflows.from_config(TINY, 1)
    yield workflows.from_config(TINY, 2)
    yield generate_app(AppParams(n_tasks=(8, 12)), 3)


@pytest.mark.parametrize("dtype", ["float64", "bfloat16"])
def test_sparse_plan_is_the_relax_plan(dtype):
    """SparsePlan computes what reference.RelaxPlan computes, bit for bit,
    without padding every wave to the widest join."""
    m = reference.Machine(TINY["machine"])
    rng = np.random.default_rng(0)
    for app in _small_apps():
        n = app.n_subtasks
        core = rng.integers(0, m.n_cores, n)
        start = rng.permutation(n).astype(float)
        # a valid per-core order needs only the schedule's start order
        # to respect dependencies: order by a topological level
        level = reference._waves(n, [[p for p, _ in ps] for ps in app.preds()])
        start = np.asarray(level, float) * 1000.0 + start / n
        dense = reference.schedule_plan(app, m, core, start)
        sparse = workflows.SparsePlan(app, m, core, start)
        dur = np.stack([np.array([t[0] for t in app.times])
                        * reference.jitter_factors(n, k, 0.01)
                        for k in range(4)])
        rel = np.zeros(n)
        assert np.array_equal(dense.run(dur, rel, dtype),
                              sparse.run(dur, rel, dtype))


def _run(harness, cell, seed=11):
    return harness.execute(cell, seed, 0.5, False, control=True)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_program_passes_and_control_fails(harness, tiny_montage, seed):
    result = _run(harness, tiny_montage, seed)
    assert result["correct"], result["checks"]
    c = result["control_checks"]["texec_gap"]
    assert c["value"] >= 50 * c["limit"], result["control_checks"]


def test_dropping_the_last_leaf_group_of_every_join_is_not_correct(
        harness, tiny_montage, monkeypatch):
    from repro.core import lowering, sim_engine
    batch_scenarios = sim_engine.batch_scenarios

    def dropped(scenarios):
        batch = batch_scenarios(scenarios)
        pred, lat, volbw = (np.array(x) for x in
                            (batch.pred, batch.pred_lat, batch.pred_volbw))
        n_dropped = 0
        for i, sa in enumerate(scenarios):
            lay = lowering.pred_layout(sa.graph)
            leaf = {int(r) - lay.n_subtasks for r in lay.edge_row
                    if r >= lay.n_subtasks}
            last = {int(lay.join_consumer[j]): j for j in sorted(leaf)}
            for j in last.values():
                at = (i, lay.join_row[j], lay.join_col[j])
                pred[at], lat[at], volbw[at] = batch.max_subtasks, \
                    -np.inf, -np.inf
                n_dropped += 1
        assert n_dropped
        return dataclasses.replace(batch, pred=pred, pred_lat=lat,
                                   pred_volbw=volbw)
    monkeypatch.setattr(sim_engine, "batch_scenarios", dropped)
    r = _run(harness, tiny_montage)
    assert not r["correct"]
    assert r["checks"]["texec_gap"]["value"] > \
        r["checks"]["texec_gap"]["limit"]


def test_app_data_is_what_the_reference_reads():
    app = workflows.from_config(TINY, 2)
    assert isinstance(app, AppData)
    g = 4
    pairs = 2 * g * (g - 1) + (g - 1) ** 2
    assert app.n_subtasks == 2 * g * g + pairs + 6
    fan_in = [len(p) for p in app.preds()]
    assert max(fan_in) == pairs                     # mConcatFit
