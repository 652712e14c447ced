"""Workload kind ``suite``: repeated validation of a suite of scheduled
applications under execution-time jitter,
``simulate_suite(..., jitter=..., backend="pallas")`` — the paper's Eq. 4
check of predicted against executed time, over a sweep of draws.

The applications are pinned by the traffic file's ``graph_seeds``, and
their schedules are data: the file the traffic's ``schedules`` names
(relative to ``bench/``, written once by ``bench/make_schedules.py``)
gives each subtask's core and start, so neither side of the comparison
is fed by the program's scheduler. Each call of the window covers every
application ``draws`` times, each scenario with its own jitter seed
drawn from the run's seed. Set-up makes one call of the same shape, so
the window compiles nothing.

The comparison covers every scenario of every call: its ``t_exec``
against the reference's float64 relaxation of the same schedule with the
same jitter draws.
"""

from __future__ import annotations

import json
import time

import numpy as np

from .. import program, reference, work as work_lib
from ..discover import BENCH
from ..synth import AppParams, generate_app


def _layout(tr: dict, apps) -> list[tuple[np.ndarray, np.ndarray]]:
    """(core, start) per application from the traffic's schedule file."""
    data = json.loads((BENCH / tr["schedules"]).read_text())
    rows = {int(a["graph_seed"]): a for a in data["apps"]}
    layout = []
    for seed, app in zip(tr["graph_seeds"], apps):
        row = rows[int(seed)]
        if row["n_subtasks"] != app.n_subtasks:
            raise ValueError(f"schedule of graph {seed} places "
                             f"{row['n_subtasks']} subtasks, the graph has "
                             f"{app.n_subtasks}")
        layout.append((np.asarray(row["core"], np.int64),
                       np.asarray(row["start"], np.float64)))
    return layout


def setup(run):
    from repro.core.sim_engine import simulate_suite

    cfg, tr = run.config, run.traffic
    params = AppParams.from_dict(cfg["apps"])
    apps = [generate_app(params, int(s)) for s in tr["graph_seeds"]]
    ref_machine = reference.Machine(cfg["machine"])
    machine = program.machine(cfg)
    graphs = [program.graph(a) for a in apps]
    layout = _layout(tr, apps)
    schedules = [program.timeline(a, machine, core, start)
                 for a, (core, start) in zip(apps, layout)]
    draws = int(tr["draws"])
    state = {"apps": apps, "ref_machine": ref_machine, "layout": layout,
             "graphs": graphs * draws, "schedules": schedules * draws,
             "machine": machine, "simulate_suite": simulate_suite,
             "draws": draws}
    _call(run, state, tag=0)
    return state


def _seeds(run, state, tag: int) -> np.ndarray:
    return run.rng(2, tag).integers(0, 2**31 - 1, len(state["graphs"]))


def _call(run, state, tag: int):
    seeds = _seeds(run, state, tag)
    res = state["simulate_suite"](
        state["graphs"], state["machine"], state["schedules"],
        jitter=float(run.traffic["jitter"]), seeds=[int(s) for s in seeds],
        backend=run.traffic["backend"])
    return seeds, np.asarray(res.t_exec, np.float64)


def window(run, state):
    calls = []
    t0 = time.perf_counter()
    k = 0
    while time.perf_counter() - t0 < run.seconds:
        k += 1
        with run.span("suite"):
            calls.append(_call(run, state, tag=k))
    span = time.perf_counter() - t0
    n = sum(len(t) for _, t in calls)
    run.info.update({"calls": k, "scenarios": n, "window_s": span})
    return {"calls": calls, "span": span, "attempted": n,
            "failed": int(sum((~np.isfinite(t) | (t <= 0)).sum()
                              for _, t in calls))}


def metrics(run, state, out):
    return {"suite_scenarios_per_s": out["attempted"] / out["span"]}


def work(run, state, out):
    """Relaxation work of one call: one pass over each scenario."""
    one = {}
    for a in state["apps"]:
        p = max(len(ps) for ps in a.preds()) + 1     # + the in-order edge
        one = work_lib.add(one, work_lib.relax_pass(state["draws"],
                                                    a.n_subtasks, p))
    return {"suite": one}


def release(state, out):
    for key in ("graphs", "schedules", "machine", "simulate_suite"):
        state.pop(key, None)


def check(run, state, out, control: bool = False) -> dict:
    """Every scenario's ``t_exec`` against the float64 reference. With
    ``control`` the program's answers are replaced by the reference in
    bfloat16, one precision below the program's float32."""
    lim = run.traffic["limits"]
    m: reference.Machine = state["ref_machine"]
    jitter = float(run.traffic["jitter"])
    n_apps = len(state["apps"])
    gap, bad = 0.0, out["failed"]
    for j, (a, (core, start)) in enumerate(zip(state["apps"],
                                               state["layout"])):
        plan = reference.schedule_plan(a, m, core, start)
        base = np.array([m.exec_time(a, s, int(core[s]))
                         for s in range(a.n_subtasks)])
        rows, got = [], []
        for seeds, t_exec in out["calls"]:
            if len(t_exec) != len(seeds):
                bad += 1
                continue
            for i in range(j, len(seeds), n_apps):
                rows.append(base * reference.jitter_factors(
                    a.n_subtasks, int(seeds[i]), jitter))
                got.append(t_exec[i])
        if not rows:
            continue
        rows = np.stack(rows)
        ref = plan.run(rows, np.zeros(a.n_subtasks)).max(axis=1)
        if control:
            got = plan.run(rows, np.zeros(a.n_subtasks), "bfloat16").max(axis=1)
        got = np.asarray(got)
        gap = max(gap, float(np.max(np.abs(got - ref) / ref)))
    if not out["calls"]:
        bad += 1
    return {"texec_gap": {"value": gap, "limit": lim["texec_gap"]},
            "missing": {"value": bad, "limit": 0}}
