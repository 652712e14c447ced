"""Manifest of every compiled entry point tracecheck must prove.

The analyzer (:mod:`repro.analysis.tracecheck`) is only as good as its
coverage: a hot path that never lands in this manifest is a hot path
nobody statically checks. So registration is *explicit* — each
:class:`EntryPoint` names one compiled callable (a jitted step, a
Pallas wrapper, an abstractly-compiled pipeline stage) and knows how to
build representative arguments per suite size, mirroring the 8/64/256
core suites of ``repro.analysis.verify``:

* ``8core`` — ``dell_poweredge_1950``, 3 synthetic apps of 8–12 tasks;
* ``64core`` — ``hp_bl260c``, 2 apps of 20–30 tasks;
* ``256core`` — ``cluster_of_multicores(n_blades=32)``, 2 apps of
  30–40 tasks;
* ``model`` — model-stack shapes (reduced configs concretely, full
  ``ARCHS`` entries abstractly via ``jax.eval_shape`` — no weights are
  ever allocated for the 2B-parameter cost cross-checks).

A build returns a :class:`Built`: the callable, its (concrete or
abstract) arguments, a same-shape/different-value argument *sweep* for
the recompilation detector, and optionally a :class:`CostRef` — the
``autoplace/costs.py`` roofline terms the extracted HLO costs must
agree with, within the stated ratio bounds.

Adding a new compiled entry point to the repo? Register it here (or
via :func:`register_entrypoint` next to its definition) in the same PR
— the CI gate ``python -m repro.analysis.tracecheck --quick`` walks
this manifest and nothing else.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

__all__ = ["Built", "CostRef", "EntryPoint", "MANIFEST", "SUITES",
           "manifest", "register_entrypoint"]

#: suite names understood by the builders below
SUITES = ("8core", "64core", "256core", "model")


@dataclass(frozen=True)
class CostRef:
    """Roofline reference terms for the cost cross-check pass.

    ``flops``/``hbm_bytes`` come from ``autoplace.costs.unit_costs``
    (or a closed-form count for non-model entries); the extracted HLO
    ``dot_flops / flops`` ratio must land inside ``flops_bounds`` and
    ``traffic_bytes / hbm_bytes`` inside ``bytes_bounds`` — the same
    analytic-vs-HLO tolerance contract ``tests/test_autoplace.py``
    pins for the placement cost model."""

    flops: float
    hbm_bytes: float
    flops_bounds: tuple[float, float] = (0.85, 1.15)
    bytes_bounds: tuple[float, float] = (0.05, 20.0)
    source: str = "autoplace.unit_costs(analytic)"


@dataclass
class Built:
    """One traceable instantiation of an entry point.

    ``fn`` takes only arrays (statics closed over); ``args`` may be
    concrete arrays or ``jax.ShapeDtypeStruct`` (``abstract=True`` —
    cost/structure passes only, no execution). ``sweep`` holds extra
    argument tuples of identical shapes/dtypes but different values:
    a correctly-jitted entry point must not retrace on any of them.
    ``jfn`` overrides the default ``jax.jit(fn, static_argnums=...)``
    when the entry point ships pre-jitted (the device GA's
    ``generation_step``)."""

    fn: Callable
    args: tuple
    sweep: tuple = ()
    abstract: bool = False
    static_argnums: tuple[int, ...] = ()
    jfn: Optional[Callable] = None
    cost_ref: Optional[CostRef] = None


@dataclass(frozen=True)
class EntryPoint:
    """A registered compiled entry point: name + per-suite builder.

    ``const_bytes_limit`` caps the size of arrays the jaxpr may capture
    as constants (the "closed over the population" bug class);
    ``allow_f64`` / ``allow_upcast`` relax the dtype pass for entries
    whose promotion is deliberate (bf16 models accumulate norms in
    f32)."""

    name: str
    build: Callable[[str], Built]
    suites: tuple[str, ...] = ("8core",)
    const_bytes_limit: int = 64 * 1024
    allow_f64: bool = False
    allow_upcast: bool = False
    doc: str = ""


# ---------------------------------------------------------------------------
# suite builders (mirror analysis.verify._sweep)
# ---------------------------------------------------------------------------

def _suite_workload(suite: str, seed: int = 0):
    """(machine, graphs) of one scheduling suite."""
    from ..core import (SynthParams, cluster_of_multicores,
                        dell_poweredge_1950, generate_app, hp_bl260c)

    def apps(lo, hi, n, base):
        return [generate_app(SynthParams(n_tasks=(lo, hi)), seed=base + i)
                for i in range(n)]

    if suite == "8core":
        return dell_poweredge_1950(), apps(8, 12, 3, seed)
    if suite == "64core":
        return hp_bl260c(), apps(20, 30, 2, seed + 10)
    if suite == "256core":
        return cluster_of_multicores(n_blades=32), apps(30, 40, 2,
                                                        seed + 20)
    raise ValueError(f"unknown scheduling suite {suite!r} "
                     f"(have {SUITES[:3]})")


def _scheduled_batch(suite: str):
    """A lowered ScenarioBatch of engine-scheduled suite apps."""
    from ..core import (batch_scenarios, get_scheduler, lower_scenario)
    machine, graphs = _suite_workload(suite)
    sched = get_scheduler("engine")
    scenarios = [lower_scenario(g, machine, sched(g, machine))
                 for g in graphs]
    return machine, graphs, batch_scenarios(scenarios)


# ---------------------------------------------------------------------------
# entry builders
# ---------------------------------------------------------------------------

def _build_generation_step(suite: str) -> Built:
    import jax
    import jax.numpy as jnp

    from ..search.device import (device_inputs, generation_step,
                                 score_population)
    from ..search.ga import GAParams
    machine, graphs = _suite_workload(suite)
    graph = graphs[0]
    params = GAParams(pop_size=16, generations=2)
    inp = device_inputs(graph, machine)
    n_tasks = len(graph.tasks)
    step = generation_step(params, n_tasks=n_tasks,
                           n_cores=machine.n_cores, method="scan")

    def pop_at(seed):
        k = jax.random.PRNGKey(seed)
        pop = jax.random.randint(k, (params.pop_size, n_tasks), 0,
                                 machine.n_cores, jnp.int32)
        return (inp, k, pop, score_population(inp, pop))

    return Built(fn=step, jfn=step, args=pop_at(0),
                 sweep=(pop_at(1), pop_at(2)))


def _build_sim_relax_pop(suite: str) -> Built:
    import jax

    from ..core.lowering import relax_operands
    from ..core.sim_engine import _jitter_durations
    from ..kernels import ops
    _, _, batch = _scheduled_batch(suite)
    pred, lat, volbw = relax_operands(batch)
    f32 = functools.partial(np.asarray, dtype=np.float32)
    fn = functools.partial(ops.sim_relax_pop, n_steps=batch.depth)
    base = (pred, f32(lat), f32(volbw), f32(batch.duration),
            f32(batch.release))
    sweep = tuple(
        (pred, f32(lat), f32(volbw),
         f32(_jitter_durations(batch, 0.2, range(s, s + batch.n_scenarios))),
         f32(batch.release))
        for s in (1, 7))
    return Built(fn=fn, jfn=jax.jit(fn), args=base, sweep=sweep)


def _build_sched_score(suite: str) -> Built:
    import jax

    from ..core.lowering import drain_matrix
    from ..kernels import ops
    machine, graphs = _suite_workload(suite)
    drain = np.asarray(drain_matrix(graphs, machine), np.float32)
    a, c = drain.shape
    frontiers = np.zeros(c, np.float32)
    release = np.zeros(a, np.float32)
    fn = ops.sched_score
    sweep = ((drain * 1.5, frontiers + 3.0, release + 1.0),
             (drain + 0.25, frontiers + 7.0, release))
    return Built(fn=fn, jfn=jax.jit(fn), args=(drain, frontiers, release),
                 sweep=sweep)


def _build_admission_score(suite: str) -> Built:
    """The batched admission scorer exactly as
    ``online.policies.BatchedPolicy.kernel_scores`` assembles it: a
    drain matrix off the shared scenario IR, live cluster frontiers,
    per-app release floors."""
    import jax

    from ..core.lowering import drain_matrix
    from ..kernels import ops
    from ..online import ArrivalParams, OnlineAMTHA, generate_workload
    machine, _ = _suite_workload(suite)
    eng = OnlineAMTHA(machine)
    arrivals = generate_workload(ArrivalParams(), n_apps=6, seed=0)
    for a in arrivals[:3]:
        eng.admit(a)
    batch = arrivals[3:]
    drain = np.asarray(drain_matrix([a.graph for a in batch], machine),
                       np.float32)
    frontiers = np.asarray(eng.state.frontiers(), np.float32)
    release = np.asarray([a.t_arrival for a in batch], np.float32)
    fn = ops.sched_score
    sweep = ((drain, frontiers + 5.0, release + 2.0),)
    return Built(fn=fn, jfn=jax.jit(fn), args=(drain, frontiers, release),
                 sweep=sweep)


def _build_flash_attention(suite: str) -> Built:
    import jax
    import jax.numpy as jnp

    from ..kernels import ops
    b, s, hq, hkv, d = 1, 128, 4, 2, 64

    def at(seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
        return q, k, v

    def fn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True)

    return Built(fn=fn, jfn=jax.jit(fn), args=at(0), sweep=(at(1),))


def _reduced_pipeline_cfg():
    from ..configs import ARCHS, reduced
    return reduced(ARCHS["glm4-9b"]).replace(dtype="float32", n_layers=4)


def _build_pipelined_forward(suite: str) -> Built:
    """``make_pipelined_forward`` over as many pipeline stages as the
    host exposes (CI forces 4 devices via ``XLA_FLAGS``); abstract
    params/tokens — the pass suite reads structure and cost, it never
    runs the pipeline."""
    import jax
    import jax.numpy as jnp

    from ..autoplace.costs import unit_costs
    from ..launch.mesh import make_mesh
    from ..models.model import init_params
    from ..runtime.pipeline import make_pipelined_forward
    cfg = _reduced_pipeline_cfg()
    _, n_rep, _, _ = cfg.repeat_structure()
    n_stages = max(s for s in range(1, jax.device_count() + 1)
                   if n_rep % s == 0)
    mesh = make_mesh((n_stages,), ("pod",))
    fwd = make_pipelined_forward(cfg, mesh, n_stages)
    n_micro, bm, seq = 3, 2, 16
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((n_micro, bm, seq), jnp.int32)
    # roofline reference for the *per-device* partitioned program the
    # compiled HLO describes: a gpipe schedule runs
    # n_micro + n_stages - 1 steps (bubble included — idle steps still
    # execute their dots on don't-care data), each over n_rep/n_stages
    # repeat units, plus the vmapped lm head (2*d*V dots per token;
    # embedding is a gather, no dot term). The per-unit term comes
    # from unit_costs(source="hlo") — the analytic closed form is
    # pinned only at full scale (tests/test_autoplace.py) and
    # undercounts ~4x at these toy dims; the hlo term checks the
    # *assembly* instead
    unit = unit_costs(cfg, seq=seq, micro_batch=bm, source="hlo")
    head = 2.0 * bm * seq * cfg.d_model * cfg.vocab
    steps = n_micro + n_stages - 1
    units_per_stage = n_rep // n_stages
    ref = CostRef(
        flops=steps * units_per_stage * unit.flops + n_micro * head,
        hbm_bytes=steps * units_per_stage * unit.hbm_bytes,
        flops_bounds=(0.8, 1.25), bytes_bounds=(0.3, 5.0),
        source="autoplace.unit_costs(hlo) * gpipe steps "
               "(bubble-inclusive, per device) + head")
    return Built(fn=fwd, jfn=jax.jit(fwd), args=(params, tokens),
                 abstract=True, cost_ref=ref)


def _build_autoplace_unit(arch: str) -> Callable[[str], Built]:
    def build(suite: str) -> Built:
        """One repeat unit of ``arch``, compiled abstractly exactly like
        ``autoplace.costs._hlo_unit_terms`` — the cost pass re-derives
        the HLO terms and must land inside the analytic-vs-HLO ratio
        bounds ``tests/test_autoplace.py`` pins."""
        import jax
        import jax.numpy as jnp

        from ..autoplace.costs import unit_costs
        from ..configs import ARCHS
        from ..models.blocks import init_layer, layer_forward
        from ..models.model import ShardCtx
        cfg = ARCHS[arch]
        _, _, unit, _ = cfg.repeat_structure()
        seq, micro_batch = 1024, 1
        ctx = ShardCtx(mode="train")
        key = jax.random.PRNGKey(0)
        abstract_ps = [
            jax.eval_shape(lambda k=kind: init_layer(k, cfg, key))
            for kind in unit]

        def unit_fn(ps, x):
            for kind, p in zip(unit, ps):
                x, _, _ = layer_forward(kind, p, x, cfg=cfg, ctx=ctx,
                                        positions=jnp.arange(x.shape[1]))
            return x

        x = jax.ShapeDtypeStruct((micro_batch, seq, cfg.d_model),
                                 jnp.dtype(cfg.dtype))
        ana = unit_costs(cfg, seq=seq, micro_batch=micro_batch)
        lo, hi = _UNIT_FLOP_BOUNDS.get(arch, (0.6, 1.4))
        # bytes: the HLO traffic proxy counts every buffer move, the
        # analytic term only the weight + 4x-activation floor — same
        # order of magnitude is the contract (measured 9-17x here)
        ref = CostRef(flops=ana.flops, hbm_bytes=ana.hbm_bytes,
                      flops_bounds=(lo, hi), bytes_bounds=(0.5, 25.0))
        return Built(fn=unit_fn, jfn=jax.jit(unit_fn),
                     args=(abstract_ps, x), abstract=True, cost_ref=ref)
    return build


#: analytic/HLO dot-FLOP ratio bounds per arch — the same tolerances
#: ``tests/test_autoplace.py::test_analytic_vs_hlo`` pins
_UNIT_FLOP_BOUNDS = {"gemma-2b": (0.85, 1.15), "gemma2-2b": (0.60, 1.20)}


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------

_BUILTIN: tuple[EntryPoint, ...] = (
    EntryPoint(
        "search.generation_step", _build_generation_step,
        suites=("8core", "64core"),
        doc="device-GA jitted generation (select/crossover/mutate/eval)"),
    EntryPoint(
        "sim.relax_pop", _build_sim_relax_pop,
        suites=("8core", "64core", "256core"),
        doc="sim_relax_pop — the compiled core of simulate_batch/"
            "simulate_suite(backend='pallas')"),
    EntryPoint(
        "kernels.sched_score", _build_sched_score,
        suites=("8core", "64core"),
        doc="drain-estimate Pallas kernel over an (apps x cores) grid"),
    EntryPoint(
        "online.admission_score", _build_admission_score,
        suites=("8core",),
        doc="BatchedPolicy.kernel_scores operands: live drain matrix, "
            "cluster frontiers, arrival floors"),
    EntryPoint(
        "kernels.flash_attention", _build_flash_attention,
        suites=("model",),
        doc="GQA flash attention wrapper (interpret off-TPU)"),
    EntryPoint(
        "runtime.pipelined_forward", _build_pipelined_forward,
        suites=("model",),
        doc="gpipe'd LM forward over the pod mesh, reduced glm4-9b"),
    EntryPoint(
        "autoplace.unit[gemma-2b]", _build_autoplace_unit("gemma-2b"),
        suites=("model",), allow_upcast=True,
        doc="one gemma-2b repeat unit, abstract compile — cost "
            "cross-check vs the analytic roofline"),
    EntryPoint(
        "autoplace.unit[gemma2-2b]", _build_autoplace_unit("gemma2-2b"),
        suites=("model",), allow_upcast=True,
        doc="one gemma2-2b repeat unit (local/global attn pair)"),
)

_REGISTERED: list[EntryPoint] = []


def register_entrypoint(ep: EntryPoint) -> EntryPoint:
    """Add an entry point to the manifest (for subsystems that define
    their compiled callables after import, or tests planting defect
    fixtures). Returns ``ep`` so it can decorate a module constant."""
    if any(e.name == ep.name for e in manifest()):
        raise ValueError(f"entry point {ep.name!r} already registered")
    _REGISTERED.append(ep)
    return ep


def manifest() -> tuple[EntryPoint, ...]:
    """The full manifest: built-ins + runtime registrations."""
    return _BUILTIN + tuple(_REGISTERED)


#: import-time snapshot (built-ins only) — prefer :func:`manifest`
MANIFEST = _BUILTIN
