"""The boundary to the system under test: builds the program's own
machine and graph objects from the benchmark's data, and nothing else.
Every other module of the benchmark that touches the program does so
through the objects made here or through the entry points the workload
kinds call."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def import_path() -> None:
    """Put the program's ``src`` on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def machine(cfg: dict):
    """The program's ``MachineModel`` of a configuration."""
    from repro.core.machine import CommLevel, MachineModel

    from .reference import Machine
    ref = Machine(cfg["machine"])
    levels = [CommLevel(name, lat, bw) for name, lat, bw in ref.levels]
    return MachineModel(cfg["name"], list(ref.core_types),
                        list(ref.locations), levels)


def graph(app):
    """The program's ``AppGraph`` of a generated application."""
    from repro.core.mpaha import AppGraph

    g = AppGraph(n_types=app.n_types)
    for t, chain in enumerate(app.tasks):
        g.add_task(t, [app.times[s] for s in chain])
    for src, dst, vol in app.edges:
        g.add_edge(src, dst, vol)
    g.finalize()
    return g


def timeline(app, machine, core, start):
    """The program's ``Timeline`` of a schedule given as data: per subtask
    its core and start; each ends after its duration on that core."""
    from repro.core.timeline import Timeline

    tl = Timeline(machine.n_cores)
    for s in range(app.n_subtasks):
        c = int(core[s])
        tl.place(s, c, float(start[s]),
                 float(start[s]) + app.times[s][machine.core_types[c]])
    return tl
