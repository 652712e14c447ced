"""The Montage workflow generator, kept with the benchmark so that the
traffic cannot move with the program, and a plain reference of its
analytic execution that does not pad to the widest join.

Montage's DAG is the one the Pegasus workflow generator builds
(Bharathi et al., "Characterization of Scientific Workflows", WORKS
2008); its job runtimes and file sizes come from the configuration
(Juve et al., FGCS 29(3), 2013). On a ``g × g`` image grid: one
``mProjectPP`` per image; one ``mDiffFit`` per overlapping pair (each
image with its right, lower and lower-right neighbour) reading both
projections; ``mConcatFit`` reading every fit; ``mBgModel``; one
``mBackground`` per image reading the model and its projection;
``mImgtbl`` and ``mAdd`` reading every corrected image (``mAdd`` the
table too); ``mShrink``; ``mJPEG``. Each job is one task of one subtask.

The draws are made in the same order as the program's own generator
(``repro.core.workflows.montage``), so a seed names the same graph in
both. The result is :class:`bench.synth.AppData`, which
``bench/reference.py`` and ``bench/program.py`` read as they do the
§5.1 applications.
"""

from __future__ import annotations

import numpy as np

from . import reference
from .synth import AppData

KINDS = ("mProjectPP", "mDiffFit", "mConcatFit", "mBgModel", "mBackground",
         "mImgtbl", "mAdd", "mShrink", "mJPEG")


def grid_side(cfg: dict, graph_seed: int) -> int:
    """The grid side of the configuration's workflow of ``graph_seed``."""
    m = cfg["montage"]
    return int(m["grid_base"]) + int(graph_seed) % int(m["grid_span"])


def montage(g: int, seed: int, runtime_s: dict, file_bytes: dict,
            runtime_jitter=(0.8, 1.2)) -> AppData:
    """The Montage workflow of a ``g × g`` grid: each job's time is its
    type's mean times ``U(runtime_jitter)``, drawn in job order."""
    rng = np.random.default_rng(seed)
    n = g * g
    pairs = [(r * g + c, (r + dr) * g + c + dc)
             for r in range(g) for c in range(g)
             for dr, dc in ((0, 1), (1, 0), (1, 1))
             if r + dr < g and c + dc < g]
    kinds = (["mProjectPP"] * n + ["mDiffFit"] * len(pairs)
             + ["mConcatFit", "mBgModel"] + ["mBackground"] * n
             + ["mImgtbl", "mAdd", "mShrink", "mJPEG"])
    app = AppData(n_types=1)
    for sid, kind in enumerate(kinds):
        app.times.append((runtime_s[kind] * float(rng.uniform(*runtime_jitter)),))
        app.tasks.append([sid])
    diff0 = n
    concat = diff0 + len(pairs)
    bgmodel = concat + 1
    bg0 = bgmodel + 1
    imgtbl, add, shrink, jpeg = bg0 + n, bg0 + n + 1, bg0 + n + 2, bg0 + n + 3
    image, fit = file_bytes["projected_image"], file_bytes["fit"]
    corrected = file_bytes["corrected_image"]
    e = app.edges
    for k, (a, b) in enumerate(pairs):
        e += [(a, diff0 + k, image), (b, diff0 + k, image)]
    e += [(diff0 + k, concat, fit) for k in range(len(pairs))]
    e.append((concat, bgmodel, fit * len(pairs)))
    for i in range(n):
        e += [(bgmodel, bg0 + i, file_bytes["background_model"]),
              (i, bg0 + i, image)]
    e += [(bg0 + i, imgtbl, corrected) for i in range(n)]
    e.append((imgtbl, add, file_bytes["image_table_row"] * n))
    e += [(bg0 + i, add, corrected) for i in range(n)]
    e += [(add, shrink, corrected * n),
          (shrink, jpeg, file_bytes["shrunk_mosaic"])]
    return app


def from_config(cfg: dict, graph_seed: int) -> AppData:
    """The configuration's workflow of ``graph_seed``."""
    m = cfg["montage"]
    return montage(grid_side(cfg, graph_seed), graph_seed, m["runtime_s"],
                   m["file_bytes"], tuple(m["runtime_jitter"]))


class SparsePlan:
    """The analytic execution of a committed schedule, as
    :class:`bench.reference.RelaxPlan` computes it (same expressions in
    the same order), one subtask at a time in topological order over its
    own predecessors. ``RelaxPlan`` pads every wave to the widest row,
    which for a 705-way join is two hundred times the work."""

    def __init__(self, app: AppData, machine: reference.Machine, core_of,
                 start):
        n = app.n_subtasks
        core_of = [int(c) for c in core_of]
        per_core: list[list[int]] = [[] for _ in range(machine.n_cores)]
        for s in sorted(range(n), key=lambda s: (start[s], s)):
            per_core[core_of[s]].append(s)
        prev = reference.prev_from_order(n, per_core)
        self.n = n
        self.rows = []
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
            self.rows.append((np.array([p for p, _, _ in row], np.int64),
                              np.array([x for _, x, _ in row]),
                              np.array([x for _, _, x in row])))
        wave = reference._waves(n, [r[0].tolist() for r in self.rows])
        self.order = sorted(range(n), key=lambda s: wave[s])

    def run(self, duration: np.ndarray, release: np.ndarray,
            dtype: str = "float64") -> np.ndarray:
        """Ends of ``duration`` rows (R, n) under the plan, computed in
        ``dtype`` and returned as float64."""
        dt = reference.DTYPES[dtype]
        duration = np.atleast_2d(duration).astype(dt)
        release = np.broadcast_to(np.asarray(release, np.float64),
                                  duration.shape).astype(dt)
        end = np.zeros(duration.shape, dt)
        zero = dt(0.0)
        for s in self.order:
            src, lat, vbw = self.rows[s]
            ready = np.maximum(release[:, s], zero)
            if len(src):
                got = ((end[:, src] + lat.astype(dt)) + vbw.astype(dt)).max(axis=1)
                ready = np.maximum(np.maximum(got, release[:, s]), zero)
            end[:, s] = duration[:, s] + ready
        return end.astype(np.float64)
