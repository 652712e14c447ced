"""Device-resident GA: decode → lower → relax → select in one jitted step.

The host GA (``search/ga.py``) batches *fitness*, but every generation
still round-trips through Python: B candidates are decoded one at a
time on a Timeline, lowered one at a time to ScenarioArrays, and the
selection/crossover/mutation loop runs on host NumPy. This module puts
the whole generation on device:

* **Pre-lowering** (:func:`device_inputs`). One
  :func:`repro.core.lowering.population_arrays` call resolves the
  (graph, machine) pair to fixed-shape topo-ordered arrays — exec
  times, padded predecessor slots, comm matrices — built once and
  reused by *every* generation. Nothing graph- or machine-shaped is
  touched again after the first call.
* **Decode as gathers.** A population ``genes`` (B, n_tasks) turns
  into per-subtask cores, durations and per-edge (latency, vol/bw)
  lags with pure ``jnp.take`` gathers — no per-candidate loop.
* **Fitness as a fused scan** (:func:`population_ends`). The
  append-only list decode (place each subtask in the fixed topological
  order at ``max(ready, core frontier)``) is one ``lax.scan`` over
  topo slots, vmapped over candidates: finish times for the whole
  population in a single XLA computation. Alternatively
  (``method="kernel"``, the default on TPU) the same recurrence runs
  as synchronous max-plus sweeps through the population-axis Pallas
  kernel ``kernels/sim_step.sim_relax_pop_sweeps``, which stops at the
  fixpoint with S sweeps as the bound — acyclic, so both reach the
  identical fixpoint bit-for-bit (``kernels.ref.sim_relax_pop_ref`` is
  the NumPy oracle, pinned by ``tests/test_search.py``). Each kernel
  call's sweep count stays on the device in the population's
  :class:`Fitness` and is read where the search reads its best row
  anyway: the counters ``relax.sweeps``, ``relax.sweep_bound`` and
  ``relax.calls`` (``repro.obs``) sum them over a search.
* **Selection on device** (:func:`ga_search_device`). Tournament +
  elite-bias parent draws, uniform crossover and gene resampling are
  jitted ``jax.random`` array ops under one threaded PRNG key — no
  host RNG anywhere in the loop. One generation = one jitted call.

Semantics: the device decoder is **append-only** — it does not backfill
earliest gaps like the host ``decode`` (gap search is a data-dependent
Timeline walk), so device fitness can exceed host fitness where a gap
would have helped; ``decode(gap_fill=False)`` is the host-side oracle
of exactly this semantics. The ``ga <= engine`` invariant is untouched:
``ga_schedule`` re-decodes the evolved winner with the full gap-filling
host decoder and returns the better of it and the heuristic baseline.

``frozen`` placements (mid-flight recovery) stay on the host path —
``GAParams(device=True)`` falls back automatically there.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..core import lowering
from ..core.machine import MachineModel
from ..core.mpaha import AppGraph
from .local import hill_climb_device


class DevicePopulation(NamedTuple):
    """Device view of :class:`repro.core.lowering.PopulationArrays`
    (+ release floors), float32, in topo-position coordinates. A
    NamedTuple so it is a pytree — jitted steps take it as an argument
    instead of baking the arrays in as constants."""

    topo_gene: jnp.ndarray          # (S,)   int32 — gene slot per topo pos
    exec_core: jnp.ndarray          # (S, C) f32
    pred_pos: jnp.ndarray           # (S, P) int32 — pred topo pos, S pad
    pred_gene: jnp.ndarray          # (S, P) int32 — pred's gene slot
    pred_vol: jnp.ndarray           # (S, P) f32 — edge volume, 0 pad
    pred_pad: jnp.ndarray           # (S, P) bool — True at padding
    lat: jnp.ndarray                # (C, C) f32
    bw: jnp.ndarray                 # (C, C) f32
    release: jnp.ndarray            # (S,)   f32 — topo-permuted floors

    @property
    def n_subtasks(self) -> int:
        """Rows of the layout (``PopulationArrays.n_rows``): the
        subtasks and their join rows."""
        return self.topo_gene.shape[0]

    @property
    def n_cores(self) -> int:
        return self.lat.shape[0]


def device_inputs(graph: AppGraph, machine: MachineModel, *,
                  releases: dict[int, float] | None = None
                  ) -> DevicePopulation:
    """Lower once, search forever: the per-(graph, machine) constants of
    every generation, shipped to device. ``releases`` (sid -> floor)
    folds into a per-subtask floor vector like the host lowering."""
    pa = lowering.population_arrays(graph, machine)
    # prove the decode-gather contracts (topo permutation, pred-pos
    # bounds) once per (graph, machine) — the jitted generation step
    # gathers through these arrays blindly for every candidate after
    from ..analysis.ir_lint import lint_population_arrays
    lint_population_arrays(pa)
    rel = np.zeros(pa.n_subtasks, np.float32)
    if releases:
        for sid, t in releases.items():
            if not 0 <= sid < pa.n_subtasks:
                raise ValueError(f"release for unknown subtask {sid} "
                                 f"(graph has {pa.n_subtasks})")
            rel[sid] = t
    # join rows (topo_sid -1) have no floor
    rel = np.where(pa.topo_sid >= 0, rel[pa.topo_sid], np.float32(0.0))
    return DevicePopulation(
        topo_gene=jnp.asarray(pa.gene),
        exec_core=jnp.asarray(pa.exec_core, jnp.float32),
        pred_pos=jnp.asarray(pa.pred_pos),
        pred_gene=jnp.asarray(pa.pred_gene),
        pred_vol=jnp.asarray(pa.pred_vol, jnp.float32),
        pred_pad=jnp.asarray(pa.pred_pos == pa.n_rows),
        lat=jnp.asarray(pa.lat, jnp.float32),
        bw=jnp.asarray(pa.bw, jnp.float32),
        release=jnp.asarray(rel),
    )


# ---------------------------------------------------------------------------
# decode: genes -> cores / durations / per-edge lags, all gathers
# ---------------------------------------------------------------------------

def _decode_common(inp: DevicePopulation, genes: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray,
                              jnp.ndarray, jnp.ndarray]:
    """(core, duration, lag_lat, lag_volbw) of a population — (B, S) and
    (B, S, P), f32. Volume-free edges arrive instantly (the simulator's
    edge rule); pads carry ``-inf`` so they never win the readiness max."""
    b = genes.shape[0]
    s, p = inp.pred_pos.shape
    core = jnp.take(genes, inp.topo_gene, axis=1)                  # (B, S)
    dur = inp.exec_core[jnp.arange(s)[None, :], core]              # (B, S)
    src = jnp.take(genes, inp.pred_gene.reshape(-1),
                   axis=1).reshape(b, s, p)                        # (B, S, P)
    dst = core[:, :, None]
    has_comm = ~inp.pred_pad & (inp.pred_vol > 0.0)
    lag_lat = jnp.where(inp.pred_pad, -jnp.inf,
                        jnp.where(has_comm, inp.lat[src, dst], 0.0))
    lag_volbw = jnp.where(inp.pred_pad, -jnp.inf,
                          jnp.where(has_comm,
                                    inp.pred_vol / inp.bw[src, dst], 0.0))
    return core, dur, lag_lat, lag_volbw


def _candidate_ends_scan(inp: DevicePopulation, core: jnp.ndarray,
                         dur: jnp.ndarray, lag_lat: jnp.ndarray,
                         lag_volbw: jnp.ndarray) -> jnp.ndarray:
    """(S,) finish times of one candidate: the append-only list decode
    as a ``lax.scan`` over topo slots. The carry is the (S+1,) end
    vector (slot S = sentinel 0) plus the (C,) per-core frontier — the
    in-order execution edge without materialising ``prev``."""
    s = core.shape[0]
    c = inp.lat.shape[0]

    def step(carry, xs):
        ends, frontier = carry
        pos, preds, ll, lv, cr, d, r = xs
        ready = jnp.max((ends[preds] + ll) + lv)
        ready = jnp.maximum(jnp.maximum(ready, r), frontier[cr])
        e = d + jnp.maximum(ready, 0.0)
        return (ends.at[pos].set(e), frontier.at[cr].set(e)), None

    (ends, _), _ = jax.lax.scan(
        step,
        (jnp.zeros(s + 1, jnp.float32), jnp.zeros(c, jnp.float32)),
        (jnp.arange(s), inp.pred_pos, lag_lat, lag_volbw, core, dur,
         inp.release))
    return ends[:s]


def _prev_on_core(core: jnp.ndarray, sentinel: int) -> jnp.ndarray:
    """(B, S) topo position of the previous same-core subtask (the
    in-order edge), ``sentinel`` where none — per candidate, via one
    stable argsort grouping topo positions by core."""
    b, s = core.shape
    order = jnp.argsort(core, axis=1)          # stable: topo order per core
    sorted_core = jnp.take_along_axis(core, order, axis=1)
    same = sorted_core[:, 1:] == sorted_core[:, :-1]
    prev_sorted = jnp.concatenate(
        [jnp.full((b, 1), sentinel, order.dtype),
         jnp.where(same, order[:, :-1], sentinel)], axis=1)
    rows = jnp.arange(b)[:, None]
    return jnp.zeros_like(core).at[rows, order].set(prev_sorted)


def population_gather_inputs(
        inp: DevicePopulation, genes: jnp.ndarray
        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                   jnp.ndarray, jnp.ndarray]:
    """(pred, lat, volbw, duration, release) in the population-kernel
    gather shape — the device decode resolved to ``sim_relax_pop``
    inputs, the in-order core edge appended as a zero-lag column. The
    device-side twin of :func:`repro.core.lowering.relax_operands` (the
    same layout and sentinel rule, on traced arrays)."""
    s = inp.n_subtasks
    b = genes.shape[0]
    core, dur, lag_lat, lag_volbw = _decode_common(inp, genes)
    prev = _prev_on_core(core, s)[:, :, None]
    inorder = jnp.where(prev < s, 0.0, -jnp.inf)
    pred = jnp.concatenate(
        [jnp.broadcast_to(inp.pred_pos[None], (b, s, inp.pred_pos.shape[1])),
         prev], axis=2)
    lat = jnp.concatenate([lag_lat, inorder], axis=2)
    volbw = jnp.concatenate([lag_volbw, inorder], axis=2)
    rel = jnp.broadcast_to(inp.release[None], (b, s))
    return pred, lat, volbw, dur, rel


@jax.jit
def population_ends(inp: DevicePopulation, genes) -> jnp.ndarray:
    """(B, S) finish times (topo coordinates, f32) of a whole population
    — the fused scan path."""
    core, dur, lag_lat, lag_volbw = _decode_common(inp, genes)
    return jax.vmap(
        lambda c, d, l1, l2: _candidate_ends_scan(inp, c, d, l1, l2)
    )(core, dur, lag_lat, lag_volbw)


def population_ends_kernel(inp: DevicePopulation, genes, *,
                           sweeps: list | None = None) -> jnp.ndarray:
    """(B, S) finish times via the population-axis Pallas kernel
    (``kernels/sim_step.sim_relax_pop_sweeps``): synchronous max-plus
    sweeps from zeros, stopped at the fixpoint with S sweeps as the
    bound, reach the same acyclic fixpoint as the scan, bit-for-bit.
    The call appends its sweep count (an int32 device scalar) to
    ``sweeps`` when given."""
    from ..kernels import ops
    pred, lat, volbw, dur, rel = _prepare_kernel_inputs(inp, genes)
    ends, ran = ops.sim_relax_pop_sweeps(pred, lat, volbw, dur, rel,
                                         n_steps=inp.n_subtasks)
    if sweeps is not None:
        sweeps.append(ran)
    return ends


_prepare_kernel_inputs = jax.jit(population_gather_inputs)


def population_fitness_device(inp: DevicePopulation,
                              genes: jnp.ndarray, *,
                              method: str = "scan",
                              sweeps: list | None = None) -> jnp.ndarray:
    """(B,) makespans of a population — max finish time per candidate.
    A kernel call appends its sweep count to ``sweeps`` when given; the
    scan runs no sweeps."""
    if inp.n_subtasks == 0:
        return jnp.zeros(genes.shape[0], jnp.float32)
    if method == "kernel":
        ends = population_ends_kernel(inp, genes, sweeps=sweeps)
    else:
        ends = population_ends(inp, genes)
    return jnp.max(ends, axis=1)


class Fitness(NamedTuple):
    """A population's makespans and the relaxation work spent on its
    fitness calls so far, on the device: what the generation step
    carries. The scan fitness runs no sweeps and counts no calls."""

    fit: jnp.ndarray                # (B,) f32 makespans
    sweeps: jnp.ndarray             # () int32 — kernel sweeps run
    calls: jnp.ndarray              # () int32 — kernel fitness calls


def score_population(inp: DevicePopulation, genes: jnp.ndarray, *,
                     method: str = "scan") -> Fitness:
    """One :func:`population_fitness_device` call as a :class:`Fitness`."""
    ran: list = []
    fit = population_fitness_device(inp, genes, method=method, sweeps=ran)
    sweeps = functools.reduce(jnp.add, ran) if ran \
        else jnp.zeros((), jnp.int32)
    return Fitness(fit, sweeps, jnp.asarray(len(ran), jnp.int32))


def _count_relax(calls: int, sweeps: int, bound: int) -> None:
    """The relaxation counters of ``calls`` kernel fitness calls that ran
    ``sweeps`` sweeps in all, at most ``bound`` each."""
    if calls:
        obs.count("relax.calls", calls)
        obs.count("relax.sweeps", sweeps)
        obs.count("relax.sweep_bound", calls * bound)


# ---------------------------------------------------------------------------
# one jitted generation: select -> crossover -> mutate -> evaluate
# ---------------------------------------------------------------------------

def _generation(inp: DevicePopulation, key: jnp.ndarray,
                pop: jnp.ndarray, scored: Fitness, *,
                n_cores: int, elite: int, tournament: int,
                elite_bias: float, p_mut: float, method: str
                ) -> tuple[jnp.ndarray, Fitness]:
    """(new_pop, new_fitness): the full bias-elitist generation as array
    ops. Selection is tournament-of-``k`` by fitness gather; a
    ``elite_bias`` fraction of first parents comes from the sorted
    elite pool; the top ``elite`` rows survive unchanged. The new
    fitness adds this generation's relaxation work to ``scored``'s."""
    obs.count("ga.step_traces")     # the body runs only while JAX traces
    fit = scored.fit
    b, t = pop.shape
    order = jnp.argsort(fit)
    pop, fit = pop[order], fit[order]
    k_bias, k_el, k_ta, k_tb, k_x, k_m, k_g = jax.random.split(key, 7)
    rows = jnp.arange(b)
    ta = jax.random.randint(k_ta, (b, tournament), 0, b)
    a = ta[rows, jnp.argmin(fit[ta], axis=1)]
    use_elite = jax.random.uniform(k_bias, (b,)) < elite_bias
    a = jnp.where(use_elite,
                  jax.random.randint(k_el, (b,), 0, max(elite, 1)), a)
    tb = jax.random.randint(k_tb, (b, tournament), 0, b)
    bb = tb[rows, jnp.argmin(fit[tb], axis=1)]
    cross = jax.random.uniform(k_x, (b, t)) < 0.5
    child = jnp.where(cross, pop[a], pop[bb])
    mut = jax.random.uniform(k_m, (b, t)) < p_mut
    child = jnp.where(
        mut, jax.random.randint(k_g, (b, t), 0, n_cores, pop.dtype), child)
    if elite:
        child = child.at[:elite].set(pop[:elite])
    new = score_population(inp, child, method=method)
    return child, Fitness(new.fit, scored.sweeps + new.sweeps,
                          scored.calls + new.calls)


def generation_step(params: Any, *, n_tasks: int, n_cores: int,
                    method: str = "scan") -> Callable:
    """The jitted ``(inp, key, pop, fitness) -> (pop, fitness)``
    generation step :func:`ga_search_device` iterates, ``fitness`` a
    :class:`Fitness` (:func:`score_population` makes the first). Also
    built on its own by the compiled entry-point manifest
    (``analysis/entrypoints.py``), the chip smoke test
    (``chip_smoke.py``) and the TPU compile tests
    (``tests/test_tpu_compile.py``)."""
    p_mut = params.p_mutation if params.p_mutation is not None \
        else max(1.0 / max(n_tasks, 1), 0.02)
    return jax.jit(functools.partial(
        _generation, n_cores=n_cores, elite=params.elite,
        tournament=params.tournament, elite_bias=params.elite_bias,
        p_mut=p_mut, method=method))


def ga_search_device(graph: AppGraph, machine: MachineModel, *,
                     seed: int = 0, params=None,
                     elites: list[np.ndarray] | None = None,
                     releases: dict[int, float] | None = None,
                     method: str | None = None
                     ) -> tuple[np.ndarray, float]:
    """Device-resident twin of :func:`repro.search.ga.ga_search`:
    returns ``(best_vector, best_fitness)`` with the fitness under the
    append-only device semantics (float32). Deterministic under
    ``seed`` — the PRNG is one threaded ``jax.random`` key, so reruns
    (and re-jits) reproduce bit-identically. ``method`` picks the
    fitness path: ``"scan"`` (fused scan, default off-TPU) or
    ``"kernel"`` (population-axis Pallas sweeps, default on TPU)."""
    from .ga import GAParams

    par = params or GAParams()
    if method is None:
        method = "kernel" if jax.default_backend() == "tpu" else "scan"
    with obs.span("ga.inputs"):
        graph.finalize()
        n_tasks = len(graph.tasks)
        n_cores = machine.n_cores
        inp = device_inputs(graph, machine, releases=releases)
        key = jax.random.PRNGKey(seed)
        key, k0 = jax.random.split(key)
        pop = jax.random.randint(k0, (par.pop_size, n_tasks), 0,
                                 max(n_cores, 1), jnp.int32)
        if elites:
            seeded = np.array(pop)
            for i, e in enumerate(elites[:par.pop_size]):
                seeded[i] = np.asarray(e, np.int32)
            pop = jnp.asarray(seeded)

    with obs.span("ga.generations"):
        step = generation_step(par, n_tasks=n_tasks, n_cores=n_cores,
                               method=method)
        scored = score_population(inp, pop, method=method)
        obs.count("ga.candidates", par.pop_size)
        for _ in range(par.generations):
            key, kg = jax.random.split(key)
            pop, scored = step(inp, kg, pop, scored)
            obs.count("ga.generations")
            obs.count("ga.candidates", par.pop_size)
        # the best row and the relaxation tallies in one read
        best = int(jnp.argmin(scored.fit))
        vec, val, sweeps, calls = jax.device_get(
            (pop[best], scored.fit[best], scored.sweeps, scored.calls))
        vec, val = np.asarray(vec, np.int32).copy(), float(val)
        _count_relax(int(calls), int(sweeps), inp.n_subtasks)
    if par.refine_rounds > 0 and n_tasks > 0 and n_cores > 1:
        key, kr = jax.random.split(key)
        ran: list = []
        fitness = functools.partial(population_fitness_device,
                                    method=method, sweeps=ran)
        vec, val = hill_climb_device(fitness, inp, vec, val, key=kr,
                                     rounds=par.refine_rounds,
                                     moves=par.refine_moves,
                                     n_cores=n_cores)
        # each round's fitness read has synchronised: the counts are ready
        _count_relax(len(ran), int(sum(jax.device_get(ran))),
                     inp.n_subtasks)
    return vec, val
