"""The paper's §5.1 synthetic application generator, kept with the
benchmark so that the traffic cannot move with the program.

"A set of applications was selected, in which each of them varied in
terms of typical parameters: task size (5-50 seconds), number of
subtasks making up a task (3-6), communication volume among subtasks
(1000-10000), and communication probability between two different
subtasks (5-35%)." (De Giusti et al., arXiv:1004.3254, §5.1)

The draws are made in the same order as the program's own generator
(``repro.core.synth.generate_app``), so a seed names the same graph in
both. The result is plain data: per-subtask times, task chains and
communication edges. ``bench/program.py`` turns it into the program's
graph type; the reference reads it as it is.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass(frozen=True)
class AppParams:
    n_tasks: tuple[int, int]
    subtasks_per_task: tuple[int, int] = (3, 6)
    task_size_s: tuple[float, float] = (5.0, 50.0)
    comm_volume: tuple[float, float] = (1000.0, 10000.0)
    comm_probability: tuple[float, float] = (0.05, 0.35)
    volume_unit: float = 1024.0
    n_types: int = 1
    type_speed_factors: tuple[float, ...] = (1.0, 1.6, 0.75)
    hetero_noise: float = 0.05

    @classmethod
    def from_dict(cls, d: dict) -> "AppParams":
        return cls(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in d.items()})


@dataclass
class AppData:
    """One application: ``times[s][t]`` is subtask ``s`` on processor
    type ``t``; ``tasks[t]`` the chain of task ``t`` in order; ``edges``
    the inter-task messages ``(src, dst, bytes)``."""

    n_types: int
    times: list[tuple[float, ...]] = field(default_factory=list)
    tasks: list[list[int]] = field(default_factory=list)
    edges: list[tuple[int, int, float]] = field(default_factory=list)

    @property
    def n_subtasks(self) -> int:
        return len(self.times)

    def preds(self) -> list[list[tuple[int, float]]]:
        """Per subtask, its predecessors ``(sid, bytes)``: the previous
        subtask of its chain (0 bytes) and every incoming message."""
        out: list[list[tuple[int, float]]] = [[] for _ in self.times]
        for chain in self.tasks:
            for a, b in zip(chain, chain[1:]):
                out[b].append((a, 0.0))
        for src, dst, vol in self.edges:
            out[dst].append((src, vol))
        return out


def generate_app(params: AppParams, seed: int) -> AppData:
    rng = np.random.default_rng(seed)
    n_tasks = int(rng.integers(params.n_tasks[0], params.n_tasks[1] + 1))
    comm_p = float(rng.uniform(*params.comm_probability))
    app = AppData(n_types=params.n_types)
    for t in range(n_tasks):
        n_st = int(rng.integers(params.subtasks_per_task[0],
                                params.subtasks_per_task[1] + 1))
        total = float(rng.uniform(*params.task_size_s))
        shares = rng.dirichlet(np.ones(n_st)) * total
        chain = []
        for w in shares:
            per_type = []
            for ty in range(params.n_types):
                f = params.type_speed_factors[ty % len(params.type_speed_factors)]
                noise = float(rng.uniform(1 - params.hetero_noise,
                                          1 + params.hetero_noise)) \
                    if params.n_types > 1 else 1.0
                per_type.append(max(1e-3, w * f * noise))
            chain.append(len(app.times))
            app.times.append(tuple(per_type))
        app.tasks.append(chain)
    # a random topological order of the tasks keeps the messages acyclic
    order = rng.permutation(n_tasks)
    pos = {int(t): int(i) for i, t in enumerate(order)}
    for i in range(n_tasks):
        for j in range(n_tasks):
            if i == j or pos[i] >= pos[j]:
                continue
            # rng.random() and an index drawn by rng.integers consume the
            # stream exactly as rng.uniform() and rng.choice(chain) do
            if rng.random() < comm_p:
                src = app.tasks[i][int(rng.integers(0, len(app.tasks[i])))]
                dst = app.tasks[j][int(rng.integers(0, len(app.tasks[j])))]
                vol = float(rng.uniform(*params.comm_volume)) * params.volume_unit
                app.edges.append((src, dst, vol))
    return app
