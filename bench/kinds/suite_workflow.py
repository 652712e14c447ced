"""Workload kind ``suite_workflow``: the ``suite`` kind's Eq. 4 jitter
sweep (``simulate_suite(..., jitter=..., backend="pallas")``) over
scientific workflows instead of §5.1 applications.

The workflows come from ``bench/workflows.py`` by the configuration's
family and the traffic's ``graph_seeds``; their schedules are data, as
in ``suite``. The window, its metric and the release of the program's
state are ``suite``'s. Two things differ:

* the work of one call counts real edges — per scenario, every
  predecessor edge and one in-order edge per subtask — and not the
  padded columns, which for a 705-way join would count the padding as
  about two hundred times the work;
* the comparison computes the float64 reference with
  :class:`bench.workflows.SparsePlan`, the same analytic execution as
  ``reference.RelaxPlan`` without padding every wave to the widest
  join, so a window's thousands of scenarios are checked in seconds.
"""

from __future__ import annotations

import numpy as np

from .. import program, reference, work as work_lib, workflows
from . import suite

window = suite.window
metrics = suite.metrics
release = suite.release


def setup(run):
    from repro.core.sim_engine import simulate_suite

    cfg, tr = run.config, run.traffic
    if cfg.get("family") != "montage":
        raise ValueError(f"no workflow family {cfg.get('family')!r}")
    apps = [workflows.from_config(cfg, int(s)) for s in tr["graph_seeds"]]
    machine = program.machine(cfg)
    layout = suite._layout(tr, apps)
    draws = int(tr["draws"])
    state = {"apps": apps, "ref_machine": reference.Machine(cfg["machine"]),
             "layout": layout,
             "graphs": [program.graph(a) for a in apps] * draws,
             "schedules": [program.timeline(a, machine, core, start)
                           for a, (core, start) in zip(apps, layout)] * draws,
             "machine": machine, "simulate_suite": simulate_suite,
             "draws": draws}
    suite._call(run, state, tag=0)
    return state


def work(run, state, out):
    """One relaxation pass of a call over its real edges and nodes."""
    draws = state["draws"]
    edges = sum(draws * (len(a.edges) + sum(len(c) - 1 for c in a.tasks)
                         + a.n_subtasks) for a in state["apps"])
    nodes = sum(draws * a.n_subtasks for a in state["apps"])
    return {"suite": {
        "ops": work_lib.EDGE_OPS * edges + work_lib.NODE_OPS * nodes,
        "bytes": work_lib.EDGE_BYTES * edges + work_lib.NODE_BYTES * nodes}}


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
        plan = workflows.SparsePlan(a, m, core, start)
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
