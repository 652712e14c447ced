"""The benchmark's own copies of the traffic and the machine agree with
the program where they must, and the schedules a suite mix validates
are schedules of its graphs."""

import json

import numpy as np
import pytest

from bench import discover, program, reference
from bench.synth import AppParams, generate_app


@pytest.mark.parametrize("n_tasks", [(15, 25), (120, 200)])
@pytest.mark.parametrize("seed", [0, 100, 2**31 - 2])
def test_generator_names_the_programs_graph(n_tasks, seed):
    program.import_path()
    from repro.core.synth import SynthParams
    from repro.core.synth import generate_app as program_generate

    app = generate_app(AppParams(n_tasks=n_tasks), seed)
    g = program_generate(SynthParams(n_tasks=n_tasks), seed)
    assert app.times == [st.times for st in g.subtasks]
    assert app.edges == [(e.src, e.dst, e.volume) for e in g.edges]
    assert app.tasks == [g.tasks[t] for t in sorted(g.tasks)]


@pytest.mark.parametrize("traffic", ["suite-jitter32"])
def test_suite_schedules_are_schedules_of_the_mixs_graphs(traffic):
    """The schedule file a suite mix validates places every subtask of
    each of its graphs once, each task on one core, with every guarantee
    a schedule gives."""
    tr = json.loads((discover.BENCH / "traffic" / f"{traffic}.json")
                    .read_text())
    data = json.loads((discover.BENCH / tr["schedules"]).read_text())
    cfg = json.loads((discover.BENCH / "configs" / f"{data['config']}.json")
                     .read_text())
    m = reference.Machine(cfg["machine"])
    params = AppParams.from_dict(cfg["apps"])
    assert [a["graph_seed"] for a in data["apps"]] == tr["graph_seeds"]
    for row in data["apps"]:
        app = generate_app(params, row["graph_seed"])
        core, start = np.asarray(row["core"]), np.asarray(row["start"])
        end = start + np.array([m.exec_time(app, s, int(core[s]))
                                for s in range(app.n_subtasks)])
        worst, bad = reference.violations(app, m, core, start, end, 0.0)
        assert bad == 0 and worst <= 1e-9
        assert reference.overlap(core, start, end) <= 1e-9


@pytest.mark.parametrize("config", ["bl260c-64"])
def test_reference_machine_matches_the_programs(config):
    program.import_path()
    from repro.core.lowering import machine_arrays

    bench = discover.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == config)
    cfg = json.loads((discover.ROOT / entry["file"]).read_text())
    ref = reference.Machine(cfg["machine"])
    ma = machine_arrays(program.machine(cfg))
    assert np.array_equal(ref.lat, ma.lat)
    assert np.array_equal(ref.bw, ma.bw)
