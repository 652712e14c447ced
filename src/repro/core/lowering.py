"""Shared scenario array IR: one lowering from (graph, machine, schedule).

Before this module, three subsystems each re-derived their own array
view of the same objects: ``core/engine.py`` precomputed exec/comm
matrices for the vectorized chain walk, ``kernels/sched_ref.py`` built
the ``drain_matrix`` scoring input, and the simulator walked the object
graph directly. The IR here is the single source of truth all of them
gather from:

* :class:`MachineArrays` — ``(C, C)`` comm latency/bandwidth matrices
  resolved from the location hierarchy (same-core entries are
  ``(0, inf)`` so ``lat + vol / bw`` is an exact ``0.0``), plus the
  *shared-level-instance* id per core pair — the contention domain the
  fluid simulator charges transfers against;
* :class:`GraphArrays` — the ``(S, T)`` per-type exec-time matrix and
  CSR predecessor/successor adjacency with edge volumes, in the exact
  order ``AppGraph.finalize`` materialises them (chain edge first, then
  comm edges in insertion order — event and jitter-draw order depend on
  it);
* :class:`ScenarioArrays` — one *scenario* = (graph, machine, schedule
  [, releases]): exec times gathered through ``core_types`` onto cores,
  placement arrays, per-core schedule-order arrays, and per-subtask
  release floors. This is what the array simulator executes;
* :class:`ScenarioBatch` — many scenarios padded to one fixed shape
  ``(B, S, P)`` for the batched relaxation step;
  :func:`relax_operands` appends the in-order core edge as one more
  column, the ``(B, S, P+1)`` form every relaxation reads (the NumPy
  sweeps of ``core/sim_engine.py`` and the ``sim_relax_pop`` kernel).
  Scenarios may mix machines and graphs freely — the lowering already
  resolved everything to per-edge lags, so core counts never appear in
  the batch. A batch of repeated scenarios (a jitter sweep) keeps its
  ``P``-wide edge arrays at one row per distinct scenario and maps the
  ``B`` scenarios onto them (``rows``); :func:`expand_rows` gives the
  ``B``-row view;
* :class:`PredLayout` — the bounded predecessor layout both padded
  forms (the batch and :class:`PopulationArrays`) share: no row carries
  more than :data:`ROW_COLUMNS` columns, the in-order column included,
  and a subtask with more predecessors reads them through a small tree
  of *join rows* (zero duration, no core, no in-order edge). Max is
  associative and a join slot's lags are 0, so ends are bit-identical
  to an unbounded layout.

All arrays are frozen (``writeable=False``): consumers share them.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import numpy as np

from .. import obs
from .machine import MachineModel
from .mpaha import AppGraph


def _frozen(a: np.ndarray) -> np.ndarray:
    a.setflags(write=False)
    return a


# ---------------------------------------------------------------------------
# machine lowering
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MachineArrays:
    """Per-machine constants, cached on the machine object."""

    n_cores: int
    n_types: int
    core_types: np.ndarray          # (C,)   int32
    lat: np.ndarray                 # (C, C) f64, 0 on the diagonal
    bw: np.ndarray                  # (C, C) f64, inf on the diagonal
    pair_instance: np.ndarray       # (C, C) int32, -1 diag; shared-level id
    inst_level: np.ndarray          # (I,)   int32 — hierarchy depth per id
    inst_lat: np.ndarray            # (I,)   f64
    inst_bw: np.ndarray             # (I,)   f64

    @property
    def n_instances(self) -> int:
        return len(self.inst_level)


def machine_arrays(machine: MachineModel) -> MachineArrays:
    cached = getattr(machine, "_machine_arrays", None)
    if cached is not None and cached.n_cores == machine.n_cores:
        return cached
    n = machine.n_cores
    lat = np.zeros((n, n))
    bw = np.full((n, n), np.inf)
    pair = np.full((n, n), -1, np.int32)
    # instance key exactly as the fluid simulator forms it: the hierarchy
    # depth plus both location prefixes above it (equal for first-differ
    # pairs, kept verbatim for the same-leaf fallback)
    ids: dict[tuple, int] = {}
    inst_level: list[int] = []
    for a in range(n):
        for b in range(n):
            if a == b:
                continue
            d = machine.level_index(a, b)
            lvl = machine.levels[d]
            lat[a, b] = lvl.latency
            bw[a, b] = lvl.bandwidth
            key = (d, machine.locations[a][:d], machine.locations[b][:d])
            iid = ids.setdefault(key, len(ids))
            if iid == len(inst_level):
                inst_level.append(d)
            pair[a, b] = iid
    levels = np.asarray(inst_level, np.int32)
    ma = MachineArrays(
        n_cores=n, n_types=machine.n_types,
        core_types=_frozen(np.asarray(machine.core_types, np.int32)),
        lat=_frozen(lat), bw=_frozen(bw), pair_instance=_frozen(pair),
        inst_level=_frozen(levels),
        inst_lat=_frozen(np.array([machine.levels[d].latency for d in levels])),
        inst_bw=_frozen(np.array([machine.levels[d].bandwidth for d in levels])),
    )
    machine._machine_arrays = ma
    return ma


def comm_matrices(machine: MachineModel) -> tuple[np.ndarray, np.ndarray]:
    """(latency, bandwidth) matrices over core pairs — the values
    ``comm_time`` would produce, with same-core entries ``(0, inf)`` so
    ``lat + vol / bw`` short-circuits to an exact ``0.0``."""
    ma = machine_arrays(machine)
    return ma.lat, ma.bw


# ---------------------------------------------------------------------------
# graph lowering
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GraphArrays:
    """Machine-independent arrays of one MPAHA graph."""

    n_subtasks: int
    n_tasks: int
    n_types: int
    exec_type: np.ndarray           # (S, T) f64 — V_i(s, p) of the paper
    task_of: np.ndarray             # (S,)   int32
    pred_ptr: np.ndarray            # (S+1,) int32 — CSR over graph.preds
    pred_sid: np.ndarray            # (E,)   int32
    pred_vol: np.ndarray            # (E,)   f64
    succ_ptr: np.ndarray            # (S+1,) int32 — CSR over graph.succs
    succ_sid: np.ndarray            # (E,)   int32
    succ_vol: np.ndarray            # (E,)   f64

    def preds_of(self, sid: int) -> list[tuple[int, float]]:
        lo, hi = self.pred_ptr[sid], self.pred_ptr[sid + 1]
        return list(zip(self.pred_sid[lo:hi].tolist(),
                        self.pred_vol[lo:hi].tolist()))


def _csr(adj: list[list[tuple[int, float]]]
         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    ptr = np.zeros(len(adj) + 1, np.int32)
    sid, vol = [], []
    for i, row in enumerate(adj):
        ptr[i + 1] = ptr[i] + len(row)
        for s, v in row:
            sid.append(s)
            vol.append(v)
    return (_frozen(ptr), _frozen(np.asarray(sid, np.int32)),
            _frozen(np.asarray(vol, dtype=np.float64)))


def graph_arrays(graph: AppGraph) -> GraphArrays:
    """Lower one graph; cached on the graph, invalidated the same way
    ``finalize`` detects mutation (subtask/edge counts)."""
    fp = (len(graph.subtasks), len(graph.edges))
    cached = getattr(graph, "_graph_arrays", None)
    if cached is not None and cached[0] == fp:
        obs.count("lower.graph_arrays.hit")
        return cached[1]
    obs.count("lower.graph_arrays.miss")
    graph.finalize()
    pred_ptr, pred_sid, pred_vol = _csr(graph.preds)
    succ_ptr, succ_sid, succ_vol = _csr(graph.succs)
    ga = GraphArrays(
        n_subtasks=graph.n_subtasks, n_tasks=len(graph.tasks),
        n_types=graph.n_types,
        exec_type=_frozen(np.array([st.times for st in graph.subtasks],
                                   dtype=np.float64).reshape(
                                       graph.n_subtasks, graph.n_types)),
        task_of=_frozen(np.asarray([st.task_id for st in graph.subtasks],
                                   np.int32)),
        pred_ptr=pred_ptr, pred_sid=pred_sid, pred_vol=pred_vol,
        succ_ptr=succ_ptr, succ_sid=succ_sid, succ_vol=succ_vol,
    )
    graph._graph_arrays = (fp, ga)
    return ga


def _exec_core(ga: GraphArrays, ma: MachineArrays) -> np.ndarray:
    """(S, C) exec times gathered through ``core_types``, cached on the
    frozen GraphArrays keyed by the machine's MachineArrays identity —
    every scenario of one (graph, machine) pair (a whole GA population,
    every generation) shares one gather instead of paying O(S·C) each."""
    cached = ga.__dict__.get("_exec_core")
    if cached is None or cached[0] is not ma:
        cached = (ma, _frozen(ga.exec_type[:, ma.core_types]))
        object.__setattr__(ga, "_exec_core", cached)
    return cached[1]


def exec_matrix(graph: AppGraph, machine: MachineModel) -> np.ndarray:
    """(S, C) exec times gathered through ``core_types`` — the §3.3
    chain-walk input of the array engine."""
    return _exec_core(graph_arrays(graph), machine_arrays(machine))


def drain_matrix(graphs: list[AppGraph], machine: MachineModel) -> np.ndarray:
    """(apps × cores) serial drain times — the admission-screening
    scoring input (one per-type work vector per app, gathered onto
    cores)."""
    ma = machine_arrays(machine)
    per_type = np.stack([graph_arrays(g).exec_type.sum(axis=0)
                         for g in graphs])
    return per_type[:, ma.core_types]


# ---------------------------------------------------------------------------
# fault lowering
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaultArrays:
    """A fault script resolved against one machine (``repro.faults``).

    ``fail_t`` is the per-core fail instant (``inf`` = never dies);
    ``slow`` holds per-core ``(t, factor)`` slowdown steps and
    ``degrade`` per-unordered-pair ``(t, factor)`` link steps, both in
    script order — factors compose multiplicatively in that order, so
    keeping the order is what makes every simulator's float products
    bit-identical."""

    n_cores: int
    fail_t: np.ndarray              # (C,) f64, inf = never
    slow: tuple[tuple[tuple[float, float], ...], ...]       # per core
    degrade: dict[tuple[int, int], tuple[tuple[float, float], ...]]

    @property
    def max_slow_events(self) -> int:
        return max((len(s) for s in self.slow), default=0)

    @property
    def max_degrade_events(self) -> int:
        return max((len(d) for d in self.degrade.values()), default=0)


def lower_faults(n_cores: int,
                 script: Any) -> FaultArrays | None:
    """Lower a fault script (anything exposing the ``FaultScript``
    views: ``validate`` / ``fail_times`` / ``slow_events`` /
    ``degrade_events``) against a core count. ``None`` and already
    lowered :class:`FaultArrays` pass through, and an empty script
    lowers to ``None`` so the fault-free hot paths stay untouched."""
    if script is None or isinstance(script, FaultArrays):
        return script
    script.validate(n_cores)
    if not script.events:
        return None
    return FaultArrays(
        n_cores=n_cores,
        fail_t=_frozen(np.asarray(script.fail_times(n_cores), np.float64)),
        slow=tuple(tuple(s) for s in script.slow_events(n_cores)),
        degrade={k: tuple(v) for k, v in script.degrade_events().items()},
    )


# ---------------------------------------------------------------------------
# scenario lowering
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ScenarioArrays:
    """One (graph, machine, schedule[, releases[, faults]]) scenario."""

    graph: GraphArrays
    machine: MachineArrays
    exec_core: np.ndarray           # (S, C) f64 — exec_type through core_types
    core_of: np.ndarray             # (S,)   int32 — assigned core per subtask
    start: np.ndarray               # (S,)   f64 — scheduled interval
    end: np.ndarray                 # (S,)   f64
    order_ptr: np.ndarray           # (C+1,) int32 — per-core order, CSR
    order_sid: np.ndarray           # (S,)   int32
    release: np.ndarray             # (S,)   f64 — floor on start (0 = free)
    release_order: np.ndarray       # int32 — sids with a floor, in the
    #   caller's dict-insertion order (release events enter the event
    #   heap in this order; ties in time break by it, like the seed)
    fault: FaultArrays | None = None        # degraded-run replay, or None

    @property
    def n_subtasks(self) -> int:
        return self.graph.n_subtasks

    @property
    def t_est(self) -> float:
        """The schedule's makespan — the paper's predicted T_est."""
        return float(self.end.max()) if len(self.end) else 0.0

    def duration(self) -> np.ndarray:
        """(S,) exec time on the assigned core (no jitter)."""
        if not len(self.core_of):
            return np.zeros(0)
        return self.exec_core[np.arange(len(self.core_of)), self.core_of]

    def prev_on_core(self) -> np.ndarray:
        """(S,) sid of the preceding subtask in the core's schedule
        order, or -1 — the implicit in-order execution edge."""
        prev = np.full(self.graph.n_subtasks, -1, np.int64)
        for c in range(self.machine.n_cores):
            lo, hi = self.order_ptr[c], self.order_ptr[c + 1]
            row = self.order_sid[lo:hi]
            prev[row[1:]] = row[:-1]
        return prev


def _release_arrays(s_count: int, releases: dict[int, float] | None
                    ) -> tuple[np.ndarray, np.ndarray]:
    """(release floors, release insertion order) — shared by every
    candidate of a population (one releases dict applies to all)."""
    release = np.zeros(s_count)
    release_order: list[int] = []
    if releases:
        for sid, t in releases.items():
            if not 0 <= sid < s_count:
                raise ValueError(
                    f"release for unknown subtask {sid} "
                    f"(graph has {s_count}); sid namespaces drifted?")
            release[sid] = float(t)
            release_order.append(sid)
    return _frozen(release), _frozen(np.asarray(release_order, np.int32))


def _placement_scenario(ga: GraphArrays, ma: MachineArrays,
                        exec_core: np.ndarray, schedule,
                        release: np.ndarray, release_order: np.ndarray,
                        fault: FaultArrays | None) -> ScenarioArrays:
    """The per-candidate tail of :func:`lower_scenario`: only the
    placement-dependent arrays (core assignment, intervals, per-core
    order) are built here — everything shared across a population
    (graph/machine arrays, exec gather, release floors) rides in."""
    s_count = ga.n_subtasks
    if len(schedule.placements) != s_count or \
            (s_count and set(schedule.placements) != set(range(s_count))):
        raise ValueError(
            f"schedule places {len(schedule.placements)} subtasks, graph has "
            f"{s_count}; lower the merged graph for multi-app timelines")
    core_of = np.zeros(s_count, np.int32)
    start = np.zeros(s_count)
    end = np.zeros(s_count)
    for sid, p in schedule.placements.items():
        core_of[sid] = p.core
        start[sid] = p.start
        end[sid] = p.end
    order_ptr = np.zeros(ma.n_cores + 1, np.int32)
    order_sid = np.zeros(s_count, np.int32)
    k = 0
    for c in range(ma.n_cores):
        row = schedule.order_on_core(c)
        order_ptr[c + 1] = order_ptr[c] + len(row)
        order_sid[k:k + len(row)] = row
        k += len(row)
    return ScenarioArrays(
        graph=ga, machine=ma, exec_core=exec_core,
        core_of=_frozen(core_of), start=_frozen(start), end=_frozen(end),
        order_ptr=_frozen(order_ptr), order_sid=_frozen(order_sid),
        release=release, release_order=release_order, fault=fault,
    )


def lower_scenario(graph: AppGraph, machine: MachineModel,
                   schedule: Any, *,
                   releases: dict[int, float] | None = None,
                   faults: Any = None) -> ScenarioArrays:
    """Lower one scenario. The schedule must place exactly this graph's
    subtasks (the merged-graph view of an online timeline qualifies).
    ``faults`` — a ``repro.faults`` script (or prelowered
    :class:`FaultArrays`) replayed during simulation."""
    ga = graph_arrays(graph)
    ma = machine_arrays(machine)
    release, release_order = _release_arrays(ga.n_subtasks, releases)
    return _placement_scenario(ga, ma, _exec_core(ga, ma), schedule,
                               release, release_order,
                               lower_faults(ma.n_cores, faults))


# ---------------------------------------------------------------------------
# batching — fixed (B, S, P) shape for the relaxation step
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ScenarioBatch:
    """Scenarios padded to one shape. ``pad`` (== S) is the sentinel
    index: gather targets for missing predecessors / first-on-core
    subtasks point at an always-zero slot, and their lags are -inf so
    they never win the readiness max. Rows ``n_sub..n_rows-1`` of a
    scenario are the join rows of its :class:`PredLayout`: zero
    duration and release, no in-order edge, never failing; each ends at
    the latest lagged end of the edges it carries."""

    n_scenarios: int
    max_subtasks: int               # S (padded rows, join rows included)
    max_preds: int                  # P (>= 1, <= ROW_COLUMNS - 1 when joined)
    n_sub: np.ndarray               # (B,)      int32 — valid subtask count
    n_rows: np.ndarray              # (B,)      int32 — subtasks + join rows
    duration: np.ndarray            # (B, S)    f64 — exec on assigned core
    release: np.ndarray             # (B, S)    f64
    prev: np.ndarray                # (B, S)    int64 — in-order edge, S = none
    pred: np.ndarray                # (D, S, P) int64 — dependency, S = pad
    pred_lat: np.ndarray            # (D, S, P) f64 — comm latency, -inf pad
    pred_volbw: np.ndarray          # (D, S, P) f64 — vol / bw, -inf pad
    wave: np.ndarray                # (B, S)    int32 — topological level
    t_est: np.ndarray               # (B,)      f64 — per-scenario makespan
    depth: int                      # relaxation steps to reach fixpoint
    # degraded-run replay (None on fault-free batches, keeping the hot
    # paths untouched): per-subtask views of each scenario's FaultArrays
    fail_t: np.ndarray | None = None        # (B, S) assigned core's fail, inf
    slow_t: np.ndarray | None = None        # (B, S, K) slow steps, inf pad
    slow_f: np.ndarray | None = None        # (B, S, K) factors, 1.0 pad
    deg_t: np.ndarray | None = None         # (D, S, P, K2) edge steps, inf pad
    deg_f: np.ndarray | None = None         # (D, S, P, K2) factors, 1.0 pad
    # scenario b reads row rows[b] of the P-wide edge arrays above, which
    # hold D rows, one per distinct scenario; None: D = B, row b is b's
    rows: np.ndarray | None = None          # (B,) int64, in [0, D)

    @property
    def has_faults(self) -> bool:
        return self.fail_t is not None

    @property
    def valid(self) -> np.ndarray:
        """(B, S) bool mask of real subtasks (not join rows, not pads)."""
        return np.arange(self.max_subtasks)[None, :] < self.n_sub[:, None]

    @property
    def live(self) -> np.ndarray:
        """(B, S) bool mask of rows the relaxation computes: real
        subtasks and join rows."""
        return np.arange(self.max_subtasks)[None, :] < self.n_rows[:, None]


#: the array fields of a :class:`ScenarioBatch` with one row per
#: scenario, and those with one row per distinct scenario (``rows`` maps
#: the scenarios onto them)
_ROW_FIELDS = ("n_sub", "n_rows", "duration", "release", "prev", "wave",
               "t_est", "fail_t", "slow_t", "slow_f")
_EDGE_FIELDS = ("pred", "pred_lat", "pred_volbw", "deg_t", "deg_f")


#: widest row of a padded predecessor layout, the in-order column
#: included: edge blocks of the relaxation kernel are then at most
#: (32, 8, 128), a multiple of the 8-sublane tile. The §5.1 class fits
#: (27 predecessors at most in ``paper_suite_64core``); wider joins get
#: join rows.
ROW_COLUMNS = 32


@dataclass(frozen=True)
class PredLayout:
    """Where each predecessor edge of one graph sits in a bounded layout.

    Rows ``0..S-1`` are the subtasks, rows ``S..S+J-1`` the join rows.
    A subtask with more than ``ROW_COLUMNS - 1`` predecessors has its
    edges dealt, in CSR order, into join rows of ``ROW_COLUMNS - 1``
    columns, level by level, until the references left fit its own row.
    A join row carries its edges with the lags they have to the real
    consumer's core, and its parent reads it through a slot with zero
    lags. Which edge goes where depends only on the graph, so the layout
    is built once per graph; only the lags are per scenario. A graph
    with no wide row lays out exactly as an unbounded padding would."""

    n_subtasks: int                 # S
    width: int                      # P: widest row, in-order column excluded
    edge_row: np.ndarray            # (E,) int64 — row of CSR edge e
    edge_col: np.ndarray            # (E,) int64 — its column there
    join_row: np.ndarray            # (J,) int64 — row that reads join j
    join_col: np.ndarray            # (J,) int64 — its column there
    join_consumer: np.ndarray       # (J,) int64 — real subtask join j feeds
    succs: list                     # per row: rows it feeds (wave walk)
    pred_count: list                # per row: filled predecessor columns

    @property
    def n_joins(self) -> int:
        return len(self.join_row)

    @property
    def n_rows(self) -> int:
        return self.n_subtasks + self.n_joins


def _build_layout(ga: GraphArrays) -> PredLayout:
    cap = ROW_COLUMNS - 1
    n = ga.n_subtasks
    ptr = ga.pred_ptr.astype(np.int64)
    counts = ptr[1:] - ptr[:-1]
    edge_row = np.repeat(np.arange(n), counts)
    edge_col = np.arange(len(ga.pred_sid)) - np.repeat(ptr[:-1], counts)
    join_row: list[int] = []
    join_col: list[int] = []
    join_consumer: list[int] = []

    def put(item: int, row: int, col: int) -> None:
        # item >= 0 is a CSR edge, ~j is join row j
        if item >= 0:
            edge_row[item], edge_col[item] = row, col
        else:
            join_row[~item], join_col[~item] = row, col

    def fold(s: int) -> None:
        items = list(range(int(ptr[s]), int(ptr[s + 1])))
        while len(items) > cap:
            refs = []
            for k in range(0, len(items), cap):
                j = len(join_row)
                join_row.append(-1)
                join_col.append(-1)
                join_consumer.append(s)
                for c, item in enumerate(items[k:k + cap]):
                    put(item, n + j, c)
                refs.append(~j)
            items = refs
        for c, item in enumerate(items):
            put(item, s, c)

    wide = np.flatnonzero(counts > cap).tolist()
    if wide:
        with obs.span("lower.join"):
            for s in wide:
                fold(s)
    rows = n + len(join_row)
    succs: list[list[int]] = [[] for _ in range(rows)]
    pred_count = [0] * rows
    for p, r in zip(ga.pred_sid.tolist(), edge_row.tolist()):
        succs[p].append(r)
        pred_count[r] += 1
    for j, r in enumerate(join_row):
        succs[n + j].append(r)
        pred_count[r] += 1
    return PredLayout(
        n_subtasks=n, width=max([1, *pred_count]),
        edge_row=_frozen(edge_row), edge_col=_frozen(edge_col),
        join_row=_frozen(np.asarray(join_row, np.int64)),
        join_col=_frozen(np.asarray(join_col, np.int64)),
        join_consumer=_frozen(np.asarray(join_consumer, np.int64)),
        succs=succs, pred_count=pred_count)


def pred_layout(ga: GraphArrays) -> PredLayout:
    """The graph's bounded predecessor layout, cached on the frozen
    GraphArrays (every scenario and every search candidate of the graph
    shares it)."""
    lay = ga.__dict__.get("_pred_layout")
    if lay is None:
        lay = _build_layout(ga)
        object.__setattr__(ga, "_pred_layout", lay)
    return lay


def _scenario_waves(sa: ScenarioArrays, prev: np.ndarray,
                    lay: PredLayout) -> list[int]:
    """Per-row topological level over deps ∪ in-order edges (the
    longest path from a source, in rows, minus one), join rows
    included. Wave ``w`` rows depend only on waves ``< w``, so one
    wave-ordered pass — or ``max(wave) + 1`` synchronous sweeps —
    reaches the fixpoint. Pure-Python Kahn walk: list indexing here is
    hot at batch-build time and ~10x cheaper than NumPy scalar ops. The
    graph's adjacency rides in from the layout cache; the scenario's
    in-order edge is the ``next_on_core`` inverse of ``prev``."""
    n = lay.n_subtasks
    if n == 0:
        return []
    succs, pred_count = lay.succs, lay.pred_count
    prev_l = prev.tolist()
    nxt = [-1] * n
    indeg = list(pred_count)
    for s, p in enumerate(prev_l):
        if p >= 0:
            nxt[p] = s
            indeg[s] += 1
    wave = [0] * len(indeg)
    stack = [s for s, d in enumerate(indeg) if d == 0]
    seen = 0
    while stack:
        s = stack.pop()
        seen += 1
        w1 = wave[s] + 1
        for t in succs[s]:
            if wave[t] < w1:
                wave[t] = w1
            indeg[t] -= 1
            if indeg[t] == 0:
                stack.append(t)
        t = nxt[s] if s < n else -1
        if t >= 0:
            if wave[t] < w1:
                wave[t] = w1
            indeg[t] -= 1
            if indeg[t] == 0:
                stack.append(t)
    assert seen == len(indeg), "scenario dependency graph has a cycle"
    return wave


def batch_scenarios(scenarios: list[ScenarioArrays]) -> ScenarioBatch:
    """Pad scenarios (possibly of different graphs AND machines) to one
    fixed-shape batch for the relaxation (:func:`relax_operands`), each
    graph in its :class:`PredLayout`."""
    if not scenarios:
        raise ValueError("batch_scenarios needs at least one scenario")
    b = len(scenarios)
    layouts = [pred_layout(sa.graph) for sa in scenarios]
    s_max = max(lay.n_rows for lay in layouts)
    p_max = max(lay.width for lay in layouts)
    pad = s_max
    n_sub = np.zeros(b, np.int32)
    n_rows = np.zeros(b, np.int32)
    duration = np.zeros((b, s_max))
    release = np.zeros((b, s_max))
    prev = np.full((b, s_max), pad, np.int64)
    pred = np.full((b, s_max, p_max), pad, np.int64)
    pred_lat = np.full((b, s_max, p_max), -np.inf)
    pred_volbw = np.full((b, s_max, p_max), -np.inf)
    wave = np.zeros((b, s_max), np.int32)
    t_est = np.zeros(b)
    depth = 0
    n_edges = 0
    faulty = [sa.fault for sa in scenarios]
    has_faults = any(f is not None for f in faulty)
    k_slow = max((f.max_slow_events for f in faulty if f is not None),
                 default=0)
    k_deg = max((f.max_degrade_events for f in faulty if f is not None),
                default=0)
    if has_faults:
        fail_t = np.full((b, s_max), np.inf)
        slow_t = np.full((b, s_max, k_slow), np.inf)
        slow_f = np.ones((b, s_max, k_slow))
        deg_t = np.full((b, s_max, p_max, k_deg), np.inf)
        deg_f = np.ones((b, s_max, p_max, k_deg))
    for i, (sa, lay) in enumerate(zip(scenarios, layouts)):
        n = sa.graph.n_subtasks
        n_sub[i] = n
        n_rows[i] = lay.n_rows
        if n == 0:
            continue
        duration[i, :n] = sa.duration()
        release[i, :n] = sa.release
        prev_i = sa.prev_on_core()
        has_prev = prev_i >= 0
        prev[i, :n][has_prev] = prev_i[has_prev]
        ptr, psid, pvol = sa.graph.pred_ptr, sa.graph.pred_sid, \
            sa.graph.pred_vol
        counts = (ptr[1:] - ptr[:-1]).astype(np.int64)
        dst = np.repeat(np.arange(n), counts)       # edge -> consumer sid
        row, col = lay.edge_row, lay.edge_col       # edge -> its slot
        cp = sa.core_of[psid]
        cs = sa.core_of[dst]
        # same-core / volume-free edges arrive instantly (no latency),
        # matching the event simulator; same-core bw is inf so vol/bw
        # is an exact 0.0 there already
        lag_lat = np.where(pvol <= 0.0, 0.0, sa.machine.lat[cp, cs])
        lag_volbw = np.where(pvol <= 0.0, 0.0, pvol / sa.machine.bw[cp, cs])
        pred[i, row, col] = psid
        pred_lat[i, row, col] = lag_lat
        pred_volbw[i, row, col] = lag_volbw
        n_edges += len(psid) + int(has_prev.sum())
        if sa.fault is not None:
            fl = sa.fault
            fail_t[i, :n] = fl.fail_t[sa.core_of]
            for sid in range(n):
                for k, (t, f) in enumerate(fl.slow[sa.core_of[sid]]):
                    slow_t[i, sid, k] = t
                    slow_f[i, sid, k] = f
            if fl.degrade:
                # degrade applies only to edges that pay comm, like the
                # event loop's start_transfer (a != b and volume > 0)
                for e in range(len(psid)):
                    a, c2 = int(cp[e]), int(cs[e])
                    if a == c2 or pvol[e] <= 0.0:
                        continue
                    steps = fl.degrade.get((min(a, c2), max(a, c2)))
                    for k, (t, f) in enumerate(steps or ()):
                        deg_t[i, row[e], col[e], k] = t
                        deg_f[i, row[e], col[e], k] = f
        waves_i = _scenario_waves(sa, prev_i, lay)
        wave[i, :lay.n_rows] = waves_i
        t_est[i] = sa.t_est
        depth = max(depth, max(waves_i) + 1 if waves_i else 0)
    n_joins = int((n_rows - n_sub).sum())
    if n_joins:
        # each join row is read through a slot with zero lags
        with obs.span("lower.join"):
            for i, lay in enumerate(layouts):
                if lay.n_joins:
                    jr, jc = lay.join_row, lay.join_col
                    pred[i, jr, jc] = lay.n_subtasks + np.arange(lay.n_joins)
                    pred_lat[i, jr, jc] = 0.0
                    pred_volbw[i, jr, jc] = 0.0
    obs.count("lower.join_rows", n_joins)
    obs.count("lower.edge_slots", b * s_max * (p_max + 1))
    obs.count("lower.edges", n_edges)
    fault_fields = {} if not has_faults else {
        "fail_t": _frozen(fail_t), "slow_t": _frozen(slow_t),
        "slow_f": _frozen(slow_f), "deg_t": _frozen(deg_t),
        "deg_f": _frozen(deg_f)}
    return ScenarioBatch(
        n_scenarios=b, max_subtasks=s_max, max_preds=p_max,
        n_sub=_frozen(n_sub), n_rows=_frozen(n_rows),
        duration=_frozen(duration),
        release=_frozen(release), prev=_frozen(prev), pred=_frozen(pred),
        pred_lat=_frozen(pred_lat), pred_volbw=_frozen(pred_volbw),
        wave=_frozen(wave), t_est=_frozen(t_est), depth=depth,
        **fault_fields)


def relax_operands(batch: ScenarioBatch
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(pred, lat, volbw)``, each ``(D, S, P+1)``: the batch's edges
    with the in-order core edge as the last column — ``prev`` as its
    source with zero lags where ``prev < S``, the sentinel ``S`` with
    ``-inf`` lags where a row has none. The one place that column is
    appended; every relaxation of a batch reads these operands
    (``repro.search.device.population_gather_inputs`` is the
    device-side twin for a GA population). One row per row of
    ``batch.pred``: ``D = B`` unless ``batch.rows`` is set. Cached on
    the batch."""
    cached = batch.__dict__.get("_relax_operands")
    if cached is not None:
        return cached
    prev = batch.prev
    if batch.rows is not None:
        # one row per distinct scenario: each row's first scenario
        prev = prev[np.unique(batch.rows, return_index=True)[1]]
    prev = prev[:, :, None]
    inorder = np.where(prev < batch.max_subtasks, 0.0, -np.inf)
    cached = (np.concatenate([batch.pred, prev], axis=2),
              np.concatenate([batch.pred_lat, inorder], axis=2),
              np.concatenate([batch.pred_volbw, inorder], axis=2))
    object.__setattr__(batch, "_relax_operands", cached)
    return cached


def lower_population(graph: AppGraph, machine: MachineModel,
                     schedules: list[Any], *,
                     releases: dict[int, float] | None = None
                     ) -> ScenarioBatch:
    """Lower ``B`` candidate schedules of ONE (graph, machine) pair into
    a single batch — the mapping-search fitness shape (``repro.search``
    scores whole populations through one ``simulate_batch`` call).

    Same-graph batches need no per-scenario shape search: ``S`` and
    ``P`` are fixed by the shared graph, the graph/machine arrays are
    gathered once from the caches, and only the placement-dependent
    arrays (core assignment, intervals, core order) differ per
    candidate. ``releases`` (one shared map, e.g. online admission
    floors) applies to every candidate."""
    ga = graph_arrays(graph)
    ma = machine_arrays(machine)
    exec_core = _exec_core(ga, ma)
    release, release_order = _release_arrays(ga.n_subtasks, releases)
    scenarios = [_placement_scenario(ga, ma, exec_core, s,
                                     release, release_order, None)
                 for s in schedules]
    return batch_scenarios(scenarios)


def expand_rows(batch: ScenarioBatch) -> ScenarioBatch:
    """The batch with its edge arrays at one row per scenario
    (``rows`` resolved on the host), for the host relaxations and the
    analysis; the batch itself where it has no ``rows``. Cached on the
    batch."""
    if batch.rows is None:
        return batch
    full = batch.__dict__.get("_expanded")
    if full is None:
        full = replace(batch, rows=None, **{
            f: _frozen(getattr(batch, f)[batch.rows]) for f in _EDGE_FIELDS
            if getattr(batch, f) is not None})
        object.__setattr__(batch, "_expanded", full)
    return full


def select_rows(batch: ScenarioBatch, rows) -> ScenarioBatch:
    """A batch of ``len(rows)`` scenarios, scenario ``i`` being the
    batch's scenario ``rows[i]``, each of them picked at least once —
    the jitter- or seed-sweep shape: same scenarios, different draws.
    The per-scenario fields are gathered, the edge arrays are shared
    (``ScenarioBatch.rows``), so nothing is padded, walked or gathered
    again. The counters of :func:`batch_scenarios` grow by the copies:
    the relaxation gathers every scenario of the batch."""
    if batch.rows is not None:
        raise ValueError("select_rows takes a batch without a row map")
    n = batch.n_scenarios
    rows = np.array(rows, np.int64)
    if rows.ndim != 1 or rows.min(initial=0) < 0 \
            or rows.max(initial=0) >= n:
        raise ValueError(f"rows must index the batch's {n} scenarios")
    copies = np.bincount(rows, minlength=n) - 1
    if copies.min() < 0:
        raise ValueError("rows leave a scenario of the batch out")
    s = batch.max_subtasks
    edges = (batch.pred < batch.n_sub[:, None, None]).sum(axis=(1, 2)) \
        + (batch.prev < s).sum(axis=1)  # the edges batch_scenarios counts
    obs.count("lower.join_rows", int(copies @ (batch.n_rows - batch.n_sub)))
    obs.count("lower.edge_slots",
              int(copies.sum()) * s * (batch.max_preds + 1))
    obs.count("lower.edges", int(copies @ edges))
    return replace(batch, n_scenarios=len(rows), rows=_frozen(rows), **{
        f: _frozen(getattr(batch, f)[rows]) for f in _ROW_FIELDS
        if getattr(batch, f) is not None})


def repeat_batch(batch: ScenarioBatch, k: int) -> ScenarioBatch:
    """Tile a batch ``k`` times along the scenario axis
    (:func:`select_rows`)."""
    if k <= 1:
        return batch
    return select_rows(batch, np.tile(np.arange(batch.n_scenarios), k))


# ---------------------------------------------------------------------------
# population lowering — device-resident mapping search (repro.search.device)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PopulationArrays:
    """Pre-lowered (graph, machine) constants for *device-side*
    population fitness: everything a genome needs to decode into finish
    times is resolved to fixed-shape arrays in one fixed topological
    order, so a whole GA generation is pure gathers + one scan — no
    per-candidate re-lowering, ever. All per-row arrays live in
    **topological-position coordinates** (``topo_sid`` maps back to
    sids); predecessor slots are padded to ``max_preds`` with the
    sentinel position ``n_rows`` (an always-zero end slot).

    The rows are the graph's :class:`PredLayout`: each join row sits
    right before the subtask it feeds and takes that subtask's gene, so
    its edges' lags are to the consumer's core, and its own slot in the
    consumer has zero volume. Its zero duration and the consumer coming
    next make the core frontier it passes on (the scan's carry, the
    kernel's in-order edge) one the consumer reads anyway, so the
    consumer's end is exactly the unbounded layout's.

    Built once per (graph, machine) pair and cached on the graph — the
    population axis exists only on device, this object is candidate-free.
    """

    n_tasks: int
    n_subtasks: int                 # S
    n_rows: int                     # R = S + join rows
    n_cores: int                    # C
    max_preds: int                  # P (>= 1)
    topo_sid: np.ndarray            # (R,)   int32 — position -> sid, -1 join
    gene: np.ndarray                # (R,)   int32 — gene slot of the task
    exec_core: np.ndarray           # (R, C) f64 — topo-permuted exec times
    pred_pos: np.ndarray            # (R, P) int32 — pred topo position, R pad
    pred_gene: np.ndarray           # (R, P) int32 — pred's gene slot, 0 pad
    pred_vol: np.ndarray            # (R, P) f64 — edge volume, 0 pad
    lat: np.ndarray                 # (C, C) f64
    bw: np.ndarray                  # (C, C) f64


def population_arrays(graph: AppGraph, machine: MachineModel
                      ) -> PopulationArrays:
    """Lower one (graph, machine) pair for device-resident search.

    The topological order is the same deterministic sid-ordered Kahn
    walk the host decoder uses (``search.encoding.topo_order``), so an
    append-only device decode and the host ``decode(gap_fill=False)``
    place subtasks in the same sequence."""
    import heapq

    ga = graph_arrays(graph)
    ma = machine_arrays(machine)
    cached = getattr(graph, "_population_arrays", None)
    fp = (len(graph.subtasks), len(graph.edges))
    if cached is not None and cached[0] == fp and cached[1] is ma:
        obs.count("lower.population_arrays.hit")
        return cached[2]
    obs.count("lower.population_arrays.miss")
    s = ga.n_subtasks
    lay = pred_layout(ga)
    obs.count("lower.join_rows", lay.n_joins)
    indeg = (ga.pred_ptr[1:] - ga.pred_ptr[:-1]).tolist()
    succ_ptr, succ_sid = ga.succ_ptr.tolist(), ga.succ_sid.tolist()
    heap = [i for i in range(s) if indeg[i] == 0]
    heapq.heapify(heap)
    joins_of: list[list[int]] = [[] for _ in range(s)]
    for j, c in enumerate(lay.join_consumer.tolist()):
        joins_of[c].append(s + j)           # children before parents
    order: list[int] = []                   # layout rows by position
    while heap:
        sid = heapq.heappop(heap)
        order.extend(joins_of[sid])
        order.append(sid)
        for j in range(succ_ptr[sid], succ_ptr[sid + 1]):
            t = succ_sid[j]
            indeg[t] -= 1
            if indeg[t] == 0:
                heapq.heappush(heap, t)
    assert len(order) == lay.n_rows, "graph has a cycle"
    r = lay.n_rows
    rows = np.asarray(order, np.int64)
    pos_of = np.zeros(r, np.int64)
    pos_of[rows] = np.arange(r)
    # a join row stands for its consumer: its gene, no work
    owner = np.concatenate([np.arange(s), lay.join_consumer])
    gene_of_tid = {tid: k for k, tid in enumerate(graph.tasks)}
    gene_sid = np.asarray([gene_of_tid[st.task_id] for st in graph.subtasks],
                          np.int32) if s else np.zeros(0, np.int32)
    exec_row = np.concatenate([_exec_core(ga, ma),
                               np.zeros((lay.n_joins, ma.n_cores))])
    p_max = lay.width
    pred_pos = np.full((r, p_max), r, np.int32)
    pred_gene = np.zeros((r, p_max), np.int32)
    pred_vol = np.zeros((r, p_max))
    at = (pos_of[lay.edge_row], lay.edge_col)
    pred_pos[at] = pos_of[ga.pred_sid]
    pred_gene[at] = gene_sid[ga.pred_sid]
    pred_vol[at] = ga.pred_vol
    at = (pos_of[lay.join_row], lay.join_col)
    pred_pos[at] = pos_of[s:]
    pred_gene[at] = gene_sid[lay.join_consumer]
    pa = PopulationArrays(
        n_tasks=ga.n_tasks, n_subtasks=s, n_rows=r, n_cores=ma.n_cores,
        max_preds=p_max,
        topo_sid=_frozen(np.where(rows < s, rows, -1).astype(np.int32)),
        gene=_frozen(gene_sid[owner[rows]] if s else gene_sid),
        exec_core=_frozen(exec_row[rows]),
        pred_pos=_frozen(pred_pos), pred_gene=_frozen(pred_gene),
        pred_vol=_frozen(pred_vol), lat=ma.lat, bw=ma.bw,
    )
    graph._population_arrays = (fp, ma, pa)
    return pa

