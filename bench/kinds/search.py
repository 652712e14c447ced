"""Workload kind ``search``: back-to-back mapping searches of one application,
``ga_schedule(graph, machine, params=GAParams(device=True, ...))``.

The application is pinned by the traffic file's ``graph_seed``, so every
run maps the same graph; the run's seed draws each search's own seed.
The window starts searches until ``--seconds`` have passed and lets the
last one finish; ``search_s`` is the whole time over the searches made.

Set-up warms every program the window runs with one search of a single
generation: the same generation step, initial fitness, hill-climb and
decode shapes as a full search.

The comparison reads only what ``ga_schedule`` returns: the mapping,
held to what a schedule guarantees, and its makespan as the reference
executes it. The graph is one on which the search, at these parameters,
beats the ``engine`` schedule it is seeded with, so the returned mapping
is the search's own answer: a search that leaves its population
unchanged returns the engine's schedule, or one close to it, and reads
above the makespan limit.
"""

from __future__ import annotations

import time

import numpy as np

from .. import program, reference, work as work_lib
from ..synth import AppParams, generate_app


def _params(tr: dict, **over):
    from repro.search import GAParams
    return GAParams(device=True, **dict(tr["ga"], **over))


def setup(run):
    from repro.search import ga_schedule

    cfg, tr = run.config, run.traffic
    app = generate_app(AppParams.from_dict(cfg["apps"]), int(tr["graph_seed"]))
    state = {"app": app, "graph": program.graph(app),
             "machine": program.machine(cfg),
             "ref_machine": reference.Machine(cfg["machine"]),
             "ga_schedule": ga_schedule}
    ga_schedule(state["graph"], state["machine"],
                params=_params(tr, generations=1), seed=run.seed31(0))
    return state


def window(run, state):
    tr = run.traffic
    ga_schedule, graph, machine = (state["ga_schedule"], state["graph"],
                                   state["machine"])
    params = _params(tr)
    results = []
    failed = 0
    t0 = time.perf_counter()
    k = 0
    while time.perf_counter() - t0 < run.seconds:
        k += 1
        try:
            with run.span("search"):
                best = ga_schedule(graph, machine, params=params,
                                   seed=run.seed31(1, k))
        except Exception as e:
            failed += 1
            run.info.setdefault("errors", []).append(repr(e))
            continue
        pl = best.placements
        n = graph.n_subtasks
        results.append((np.array([pl[s].core if s in pl else -1
                                  for s in range(n)]),
                        np.array([pl[s].start if s in pl else 0.0
                                  for s in range(n)]),
                        np.array([pl[s].end if s in pl else 0.0
                                  for s in range(n)])))
    span = time.perf_counter() - t0
    run.info.update({"searches": k, "window_s": span,
                     "makespans": [float(r[2].max()) for r in results]})
    return {"results": results, "span": span, "attempted": k,
            "failed": failed}


def metrics(run, state, out):
    n = len(out["results"])
    return {"search_s": out["span"] / n if n else None}


def work(run, state, out):
    """Relaxation work of each search: every candidate the parameters
    evaluate, one pass each."""
    par = _params(run.traffic)
    app = state["app"]
    cands = (par.generations + 1) * par.pop_size \
        + par.refine_rounds * par.refine_moves
    p = max(len(ps) for ps in app.preds()) + 1      # + the in-order edge
    return {"search": work_lib.relax_pass(cands, app.n_subtasks, p)}


def release(state, out):
    for key in ("graph", "machine", "ga_schedule"):
        state.pop(key, None)


def check(run, state, out, control: bool = False) -> dict:
    """Each returned mapping against what a schedule guarantees, and its
    makespan as the reference executes it (the largest over the
    searches). With ``control`` the program's timeline is replaced by
    the same one rounded to float32, one precision below the program's."""
    lim = run.traffic["limits"]
    app, m = state["app"], state["ref_machine"]
    worst, makespan, bad = 0.0, 0.0, out["failed"]
    for core, start, end in out["results"]:
        if control:
            start = start.astype(np.float32).astype(np.float64)
            end = end.astype(np.float32).astype(np.float64)
        v, b = reference.violations(app, m, core, start, end, release=0.0)
        if b:
            bad += b
            continue
        v = max(v, reference.overlap(core, start, end))
        worst = max(worst, v / max(1.0, float(end.max())))
        dur = np.array([m.exec_time(app, s, int(core[s]))
                        for s in range(app.n_subtasks)])
        plan = reference.schedule_plan(app, m, core, start)
        makespan = max(makespan, float(
            plan.run(dur, np.zeros(app.n_subtasks)).max()))
    if not out["results"]:
        bad += 1
    return {"mapping_violation": {"value": worst,
                                  "limit": lim["mapping_violation"]},
            "makespan_s": {"value": makespan, "limit": lim["makespan_s"]},
            "missing": {"value": bad, "limit": 0}}
