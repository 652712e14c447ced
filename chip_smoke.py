#!/usr/bin/env python3
"""Smoke run of the scheduling hot paths on a TPU, through the entry
points a user calls, at the sizes the repository treats as real.

    python chip_smoke.py [--seed N]      # one chip: three phases
    python chip_smoke.py --chips 4       # four chips: placed pipeline only

One chip runs three phases, each checked against a reference that does
not share the code under test:

* admission — ``make_policy("batched", scorer="kernel")`` admits a
  64-app stream on a 256-core cluster; every ``sched_score`` matrix is
  held to ``sched_score_np``;
* mapping search — the device GA (``GAParams(device=True)``) maps a
  1 090-subtask graph on the 256-core cluster; the result must verify,
  beat or tie the engine heuristic, and its kernel fitness must match
  the NumPy oracle ``pop_relax_np``;
* suite validation — ``simulate_suite(backend="pallas")`` over 22
  scenarios must match ``backend="numpy"``.

``--chips 4`` places a reduced gemma2 with ``autoplace`` on four chips,
runs the GPipe forward on them and compares its logits with the
sequential forward on one chip.

Each phase prints one JSON line with its sizes, the checks it passed
and its wall seconds, cold (compiles included) and warm. They are smoke
timings, not benchmark numbers. The last line is
``{"ok": true, "device": {...}}``. The script exits non-zero and prints
no result when JAX finds no TPU, when the ``repro`` package is not next
to it, or when any check fails. The compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TIMING_NOTE = "smoke timings, not benchmark numbers"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> str:
    if not ok:
        raise SmokeFailure(what)
    return what


def emit(phase: str, sizes: dict, checks: list[str], cold: float,
         warm: float, **extra) -> None:
    print(json.dumps({"phase": phase, "sizes": sizes, "checks": checks,
                      "wall_s": {"cold": cold, "warm": warm,
                                 "note": TIMING_NOTE}, **extra}),
          flush=True)


def has_kernel(fn, *args, **static) -> bool:
    """True when the compiled program of ``fn(*args, **static)`` holds a
    Mosaic kernel: a kernel that ran interpreted would not. ``fn`` is
    the function the phase called, so its own choice between Mosaic and
    interpret mode is what is checked."""
    import jax
    # a list argument (the admission policy passes some) is traced as
    # the array the kernel receives, not as a tuple of scalars
    args = [np.asarray(a) if isinstance(a, list) else a for a in args]
    jitted = jax.jit(functools.partial(fn, **static))
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


@contextlib.contextmanager
def recorded(name: str):
    """Swap the kernel wrapper ``ops.<name>`` for one that records every
    call; yields the list of ``(kernel, args, kwargs, out)``."""
    from repro.kernels import ops
    kernel = getattr(ops, name)
    calls = []

    def wrapper(*args, **kwargs):
        out = kernel(*args, **kwargs)
        calls.append((kernel, args, kwargs, np.asarray(out)))
        return out

    setattr(ops, name, wrapper)
    try:
        yield calls
    finally:
        setattr(ops, name, kernel)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_admission(seed: int, *, n_blades: int = 32, n_apps: int = 64,
                    k: int = 8) -> None:
    from repro.core import cluster_of_multicores
    from repro.kernels.sched_ref import sched_score_np
    from repro.online import ArrivalParams, generate_workload, make_policy

    machine = cluster_of_multicores(n_blades)
    p_large = 0.25
    # offered load 0.8 of the cluster: E[work] of the small (20 tasks x
    # 27.5 s) and large (160 tasks x 27.5 s) classes of ArrivalParams
    mean_work = (1 - p_large) * 20 * 27.5 + p_large * 160 * 27.5
    params = ArrivalParams(rate=0.8 * machine.n_cores / mean_work,
                           p_large=p_large)
    workload = generate_workload(params, n_apps=n_apps, seed=seed)

    policy = make_policy("batched", k=k, validate_each=True,
                         scorer="kernel")
    with recorded("sched_score") as calls:
        state, cold = timed(lambda: policy.run(machine, workload))
        n_cold = len(calls)
        _, warm = timed(lambda: policy.run(machine, workload))
    state.validate()

    worst = 0.0
    for _, args, _, got in calls:
        ref = sched_score_np(*args)
        np.testing.assert_allclose(got, ref, rtol=1e-6)
        worst = max(worst, float(np.max(np.abs(got - ref) /
                                        np.maximum(np.abs(ref), 1e-30))))
    kernel, args, kwargs, _ = calls[0]
    checks = [
        check(n_cold == -(-n_apps // k), f"{n_cold} sched_score batches"),
        check(len(state.apps) == n_apps, "every app admitted"),
        "state.validate() after every admission and at the end",
        f"every batch == sched_score_np (rtol 1e-6, worst rel {worst!r})",
        check(has_kernel(kernel, *args, **kwargs),
              "sched_score program holds tpu_custom_call"),
    ]
    emit("admission", {"cores": machine.n_cores, "apps": n_apps,
                       "batch_k": k, "p_large": p_large,
                       "subtasks": int(sum(a.graph.n_subtasks
                                           for a in workload))},
         checks, cold, warm)


def phase_search(seed: int, *, n_blades: int = 32,
                 n_tasks: tuple[int, int] = (240, 280), pop: int = 256,
                 generations: int = 8) -> None:
    import jax
    import jax.numpy as jnp

    from repro.analysis.verify import verify_schedule
    from repro.core import (SynthParams, cluster_of_multicores,
                            generate_app, get_scheduler)
    from repro.kernels import ops
    from repro.kernels import sim_step
    from repro.search import GAParams, ga_schedule
    from repro.search.device import (Fitness, device_inputs,
                                     generation_step,
                                     population_gather_inputs)

    machine = cluster_of_multicores(n_blades)
    graph = generate_app(SynthParams(n_tasks=n_tasks), seed=300 + seed)
    # on a TPU backend the device GA picks the Pallas fitness
    params = GAParams(device=True, pop_size=pop, generations=generations)

    best, cold = timed(lambda: ga_schedule(graph, machine, params=params,
                                           seed=seed))
    _, warm = timed(lambda: ga_schedule(graph, machine, params=params,
                                        seed=seed))
    verify_schedule(best, graph, machine)
    engine = get_scheduler("engine")(graph, machine)

    # the fitness kernel on one random population against the oracle
    inp = device_inputs(graph, machine)
    n_sub = inp.n_subtasks
    genes = jax.random.randint(jax.random.PRNGKey(seed),
                               (pop, len(graph.tasks)), 0, machine.n_cores,
                               jnp.int32)
    gathered = jax.jit(population_gather_inputs)(inp, genes)
    got = np.asarray(ops.sim_relax_pop(*gathered, n_steps=n_sub))
    # candidates are independent rows; the oracle's S NumPy sweeps over
    # the whole population take minutes, so it checks the first rows
    rows = min(pop, 32)
    ref = sim_step.pop_relax_np(*(np.asarray(x)[:rows] for x in gathered),
                                n_steps=n_sub)
    np.testing.assert_allclose(got[:rows], ref, rtol=1e-5)
    diff = float(np.max(np.abs(got[:rows] - ref)))

    zero = jnp.zeros((), jnp.int32)
    fit = Fitness(jnp.max(jnp.asarray(got), axis=1), zero, zero)
    step = generation_step(params, n_tasks=len(graph.tasks),
                           n_cores=machine.n_cores, method="kernel")
    checks = [
        "verify_schedule passes on the GA result",
        check(best.makespan() <= engine.makespan(),
              f"GA makespan {best.makespan()!r} <= engine "
              f"{engine.makespan()!r}"),
        f"kernel fitness of {rows}/{pop} candidates == pop_relax_np "
        f"(rtol 1e-5, max |diff| {diff!r})",
        check(has_kernel(ops.sim_relax_pop, *gathered, n_steps=n_sub),
              "sim_relax_pop program holds tpu_custom_call"),
        check(has_kernel(step, inp, jax.random.PRNGKey(seed), genes, fit),
              "generation_step program holds tpu_custom_call"),
    ]
    emit("search", {"cores": machine.n_cores, "tasks": len(graph.tasks),
                    "subtasks": n_sub, "pop": pop,
                    "generations": generations}, checks, cold, warm)


def phase_suite(seed: int, *, n_small: int = 20, n_large: int = 2,
                small_tasks: tuple[int, int] = (120, 200),
                large_tasks: tuple[int, int] = (240, 280),
                n_blades: int = 32) -> None:
    from repro.core import (SynthParams, cluster_of_multicores,
                            generate_app, get_scheduler, hp_bl260c)
    from repro.core.sim_engine import simulate_suite

    m64, m256 = hp_bl260c(), cluster_of_multicores(n_blades)
    small, large = SynthParams(n_tasks=small_tasks), SynthParams(
        n_tasks=large_tasks)
    graphs = ([generate_app(small, seed=100 + seed + i)
               for i in range(n_small)]
              + [generate_app(large, seed=300 + seed + i)
                 for i in range(n_large)])
    machines = [m64] * n_small + [m256] * n_large
    engine = get_scheduler("engine")
    schedules = [engine(g, m) for g, m in zip(graphs, machines)]
    seeds = range(seed, seed + len(graphs))

    def run(backend):
        return simulate_suite(graphs, machines, schedules, jitter=0.01,
                              seeds=seeds, backend=backend, verify=True)

    with recorded("sim_relax_pop") as calls:
        got, cold = timed(lambda: run("pallas"))
        _, warm = timed(lambda: run("pallas"))
    ref = run("numpy")
    np.testing.assert_allclose(got.t_exec, ref.t_exec, rtol=1e-5)
    rel = float(np.max(np.abs(got.t_exec - ref.t_exec) / ref.t_exec))

    kernel, args, kwargs, _ = calls[0]
    checks = [
        check(len(calls) == 2, "one sim_relax_pop call per pallas run"),
        "verify=True (IR lint + batch result proof) on both backends",
        f"t_exec pallas == numpy (rtol 1e-5, worst rel {rel!r})",
        check(has_kernel(kernel, *args, **kwargs),
              "sim_relax_pop program holds tpu_custom_call"),
    ]
    emit("suite", {"scenarios": len(graphs),
                   "hp_bl260c_apps": n_small, "cores_256_apps": n_large,
                   "max_subtasks": int(args[0].shape[1]),
                   "depth": int(kwargs["n_steps"])}, checks, cold, warm)


def phase_pipeline(seed: int, devices) -> None:
    """Reduced gemma2 (8 layers = 4 repeat units) placed by autoplace on
    ``len(devices)`` chips and run through the GPipe forward."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import autoplace
    from repro.configs import ARCHS, reduced
    from repro.core.machine import tpu_v5e_pod
    from repro.models.model import ShardCtx, forward, init_params
    from repro.runtime.pipeline import make_pipelined_forward

    cfg = reduced(ARCHS["gemma2-2b"]).replace(dtype="float32", n_layers=8)
    plan = autoplace.place_pipeline(cfg, tpu_v5e_pod(1, len(devices)),
                                    scheduler="engine", n_micro=3, seq=16)
    mesh = autoplace.stage_mesh(plan.stage_to_device, devices=devices)
    fwd = jax.jit(make_pipelined_forward(cfg, mesh, n_stages=plan.n_stages))
    params = init_params(cfg, jax.random.PRNGKey(seed))
    n_micro, bm, s = 3, 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (n_micro, bm, s), 0, cfg.vocab)
    placed = dict(params, groups=jax.device_put(
        params["groups"], NamedSharding(mesh, P("pod"))))
    leaf = jax.tree.leaves(placed["groups"])[0]
    per_stage = leaf.shape[0] // plan.n_stages
    landed = sorted((sh.index[0].start // per_stage, sh.device.id)
                    for sh in leaf.addressable_shards)
    stage_devices = [d for _, d in landed]

    # f32 matmuls at full precision on both sides, so the comparison
    # measures the pipeline, not bf16 pass rounding
    with jax.default_matmul_precision("highest"):
        def run():
            with mesh:
                return np.asarray(fwd(placed, tokens))
        logits, cold = timed(run)
        _, warm = timed(run)
        one = jax.device_put(params, devices[0])
        ref = np.stack([np.asarray(forward(one, {"tokens": tokens[i]}, cfg,
                                           ShardCtx(mode="train"))[0])
                        for i in range(n_micro)])
    err = float(np.max(np.abs(logits - ref)))
    want = [devices[d].id for d in plan.stage_to_device]
    checks = [
        check(bool(np.isfinite(logits).all()), "logits finite"),
        check(err < 2e-3, f"max |pipelined - sequential| {err!r} < 2e-3"),
        check(stage_devices == want,
              f"stage params on devices {stage_devices} as planned"),
        check(len(set(stage_devices)) == plan.n_stages,
              "one device per stage"),
    ]
    emit("pipeline", {"arch": cfg.name, "layers": cfg.n_layers,
                      "stages": plan.n_stages, "micro": n_micro,
                      "logits": list(logits.shape)}, checks, cold, warm,
         stage_to_device=plan.stage_to_device,
         stage_device_ids=stage_devices)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every workload generator")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the placed-pipeline phase on four "
                         "chips")
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = use_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); the phases do not run "
              f"interpreted", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache_dir": str(cache)}), flush=True)
    try:
        if args.chips == 4:
            phase_pipeline(args.seed, devices[:4])
        else:
            phase_admission(args.seed)
            phase_search(args.seed)
            phase_suite(args.seed)
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
