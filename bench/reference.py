"""Plain references for the comparison that decides ``correct``.

Nothing here imports the program or reads a table it made: the graphs
come from ``bench/synth.py``, the machine from the configuration file,
and the placements being judged are the program's answers.

* :class:`Machine` — per-core type and the communication level of each
  core pair, from the configuration (the paper's §1 rule: two cores talk
  through the lowest memory level they share).
* :class:`RelaxPlan` — the analytic execution of a mapping in one
  topological pass: a subtask ends at its duration plus the latest of
  its release, the end of the previous subtask on its core, and each
  predecessor's end plus ``latency + bytes / bandwidth`` when it sits on
  another core.
* :func:`violations` — what a committed schedule guarantees: every
  subtask placed once, each task on one core, durations as the graph
  states them, release floors, precedence with communication delay, and
  no two intervals overlapping on a core.

Each arithmetic reference takes a ``dtype``: ``float64`` is the
reference, and the next precision below the program's is the control.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from .synth import AppData

DTYPES = {"float64": np.float64, "float32": np.float32,
          "bfloat16": ml_dtypes.bfloat16}


class Machine:
    """Cores and their communication from a configuration's
    ``machine`` entry: ``shape`` is the hierarchy (outermost first, the
    last entry the cores of the innermost level), ``levels`` one
    ``{name, latency_s, bandwidth_Bps}`` per hierarchy depth."""

    def __init__(self, spec: dict):
        shape = list(spec["shape"])
        self.levels = [(lv["name"], float(lv["latency_s"]),
                        float(lv["bandwidth_Bps"])) for lv in spec["levels"]]
        if len(self.levels) != len(shape):
            raise ValueError("one level per hierarchy depth")
        self.locations = [tuple(int(x) for x in np.unravel_index(i, shape))
                          for i in range(int(np.prod(shape)))]
        self.core_types = list(spec.get("core_types",
                                        [0] * len(self.locations)))
        n = len(self.locations)
        loc = np.asarray(self.locations)
        differ = loc[:, None, :] != loc[None, :, :]        # (C, C, D)
        depth = np.where(differ.any(axis=2), differ.argmax(axis=2), -1)
        lat = np.array([lv[1] for lv in self.levels])
        bw = np.array([lv[2] for lv in self.levels])
        same = depth < 0
        self.lat = np.where(same, 0.0, lat[np.maximum(depth, 0)])
        self.bw = np.where(same, np.inf, bw[np.maximum(depth, 0)])
        self.n_cores = n

    def exec_time(self, app: AppData, sid: int, core: int) -> float:
        return app.times[sid][self.core_types[core]]


def _waves(n: int, preds: list[list[int]]) -> list[int]:
    succs: list[list[int]] = [[] for _ in range(n)]
    indeg = [len(p) for p in preds]
    for s, ps in enumerate(preds):
        for p in ps:
            succs[p].append(s)
    wave = [0] * n
    stack = [s for s in range(n) if indeg[s] == 0]
    seen = 0
    while stack:
        s = stack.pop()
        seen += 1
        for t in succs[s]:
            wave[t] = max(wave[t], wave[s] + 1)
            indeg[t] -= 1
            if indeg[t] == 0:
                stack.append(t)
    if seen != n:
        raise ValueError("dependency cycle")
    return wave


class RelaxPlan:
    """One scenario's dependency structure, in waves: subtasks of wave
    ``w`` depend only on waves before it, so each is computed once."""

    def __init__(self, app: AppData, machine: Machine, core_of,
                 prev: list[int]):
        n = app.n_subtasks
        core_of = [int(c) for c in core_of]
        rows: list[list[tuple[int, float, float]]] = []
        for s, ps in enumerate(app.preds()):
            row = []
            for p, vol in ps:
                a, b = core_of[p], core_of[s]
                if a == b or vol <= 0.0:
                    row.append((p, 0.0, 0.0))
                else:
                    row.append((p, float(machine.lat[a, b]),
                                vol / float(machine.bw[a, b])))
            if prev[s] >= 0:
                row.append((prev[s], 0.0, 0.0))
            rows.append(row)
        wave = _waves(n, [[p for p, _, _ in r] for r in rows])
        width = max(1, max(len(r) for r in rows))
        self.n = n
        self.steps = []
        by_wave: dict[int, list[int]] = {}
        for s, w in enumerate(wave):
            by_wave.setdefault(w, []).append(s)
        for w in sorted(by_wave):
            idx = np.asarray(by_wave[w])
            src = np.full((len(idx), width), n)
            lat = np.full((len(idx), width), -np.inf)
            vbw = np.full((len(idx), width), -np.inf)
            for i, s in enumerate(idx):
                for j, (p, la, vb) in enumerate(rows[s]):
                    src[i, j], lat[i, j], vbw[i, j] = p, la, vb
            self.steps.append((idx, src, lat, vbw))

    def run(self, duration: np.ndarray, release: np.ndarray,
            dtype: str = "float64") -> np.ndarray:
        """Ends of ``duration`` rows (B, n) under the plan, computed in
        ``dtype`` and returned as float64."""
        dt = DTYPES[dtype]
        duration = np.atleast_2d(duration).astype(dt)
        release = np.broadcast_to(np.asarray(release, np.float64),
                                  duration.shape).astype(dt)
        end = np.zeros((duration.shape[0], self.n + 1), dt)   # n: sentinel
        zero = dt(0.0)
        for idx, src, lat, vbw in self.steps:
            ready = ((end[:, src] + lat.astype(dt)) + vbw.astype(dt)).max(axis=2)
            ready = np.maximum(np.maximum(ready, release[:, idx]), zero)
            end[:, idx] = duration[:, idx] + ready
        return end[:, :self.n].astype(np.float64)


def prev_from_order(n: int, per_core: list[list[int]]) -> list[int]:
    prev = [-1] * n
    for row in per_core:
        for a, b in zip(row, row[1:]):
            prev[b] = a
    return prev


def schedule_plan(app: AppData, machine: Machine, core_of, start
                  ) -> RelaxPlan:
    """Plan of a committed schedule: its cores, and on each core its
    subtasks in order of start."""
    per_core: list[list[int]] = [[] for _ in range(machine.n_cores)]
    for s in sorted(range(app.n_subtasks), key=lambda s: (start[s], s)):
        per_core[int(core_of[s])].append(s)
    return RelaxPlan(app, machine, core_of,
                     prev_from_order(app.n_subtasks, per_core))


def jitter_factors(n: int, seed: int, jitter: float) -> np.ndarray:
    """Per-subtask lognormal factors of one scenario, in sid order."""
    return np.exp(np.random.default_rng(seed).normal(0.0, jitter, size=n))


def violations(app: AppData, machine: Machine, core, start, end,
               release: float) -> tuple[float, int]:
    """(largest violation in model seconds, structural faults) of one
    application's placements. ``core``/``start``/``end`` are per
    subtask; a missing placement has ``core`` -1. Structural faults are
    missing placements, cores out of range and tasks split over cores."""
    core = np.asarray(core)
    bad = int((core < 0).sum() + (core >= machine.n_cores).sum())
    for chain in app.tasks:
        if len({int(core[s]) for s in chain}) != 1:
            bad += 1
    if bad:
        return 0.0, bad
    worst = 0.0
    for s, ps in enumerate(app.preds()):
        c = int(core[s])
        d = machine.exec_time(app, s, c)
        worst = max(worst, abs((start[s] + d) - end[s]), release - start[s])
        for p, vol in ps:
            q = int(core[p])
            ready = end[p] + (float(machine.lat[q, c]) + vol / float(machine.bw[q, c]))
            worst = max(worst, ready - start[s])
    return float(worst), 0


def overlap(core, start, end) -> float:
    """Largest overlap of two intervals on one core (model seconds)."""
    core, start, end = (np.asarray(x) for x in (core, start, end))
    if len(core) < 2:
        return 0.0
    order = np.lexsort((start, core))
    c, s, e = core[order], start[order], end[order]
    same = c[1:] == c[:-1]
    if not same.any():
        return 0.0
    return float(np.max(np.where(same, e[:-1] - s[1:], 0.0), initial=0.0))
