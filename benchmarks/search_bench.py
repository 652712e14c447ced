"""Mapping-search benchmark: GA quality vs the heuristics + batched fitness.

    PYTHONPATH=src python -m benchmarks.search_bench [--quick] [--json PATH]

Three sections, appended to ``BENCH_search.json`` (one entry per run,
the same perf-trajectory convention as the other benches):

* **quality** — per scenario of the §5.1 synthetic suite: makespans of
  ``amtha``/``engine`` (identical by construction), ``heft``/``etf``
  and ``ga``, plus the GA's improvement over the engine heuristic. The
  elite-seeding invariant (GA <= engine on *every* scenario) is
  asserted row by row while it times. Full runs add 64-core and
  256-core cluster-of-multicores rows (1k+-subtask graphs) on the
  device-resident GA (``GAParams(device=True)``).
* **phases** — the per-generation cost model: the host path broken down
  into its four phases (decode every chromosome on a Timeline, lower to
  a ScenarioBatch, simulate, select/crossover/mutate) vs ONE jitted
  device generation step (``repro.search.device.generation_step``,
  warm jit cache). Reports generations/sec for both and the speedup —
  the full 8-core row asserts the device step is >= 5x the host path.
* **fitness** — the reason the host GA was affordable: scoring one
  population of B decoded candidates as a per-candidate
  ``simulate_scenario`` loop vs ONE ``lower_population`` +
  ``simulate_batch`` call (both analytic semantics, equivalence-gated
  at 1e-9 relative before timing). Reports evaluations/sec for both
  and the speedup.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.core import (SynthParams, cluster_of_multicores,
                        dell_poweredge_1950, generate_app, get_scheduler,
                        hp_bl260c, lower_population, simulate_batch,
                        simulate_scenario, validate)
from repro.search import (GAParams, decode_population, device_inputs,
                          ga_schedule)


# ---------------------------------------------------------------------------
def bench_quality(name: str, machine, params: SynthParams, n_apps: int,
                  seed: int, ga_params: GAParams) -> list[dict]:
    engine = get_scheduler("engine")
    rows = []
    for i in range(n_apps):
        app = generate_app(params, seed + i)
        mk = {}
        for sched_name in ("engine", "heft", "etf"):
            mk[sched_name] = get_scheduler(sched_name)(app, machine).makespan()
        mk["amtha"] = mk["engine"]        # placement-identical (pinned by tests)
        t0 = time.perf_counter()
        ga = ga_schedule(app, machine, seed=0, params=ga_params)
        ga_s = time.perf_counter() - t0
        validate(ga, app, machine)
        mk["ga"] = ga.makespan()
        assert mk["ga"] <= mk["engine"] + 1e-9, \
            f"elite-seeding invariant broken on {name}/{seed + i}"
        gain = 100.0 * (1.0 - mk["ga"] / mk["engine"])
        rows.append({"suite": name, "seed": seed + i,
                     "tasks": len(app.tasks), "subtasks": app.n_subtasks,
                     **{k: round(v, 3) for k, v in mk.items()},
                     "ga_gain_pct": round(gain, 2), "ga_s": round(ga_s, 3)})
        print(f"{name:>8} app {seed + i:3d} ({len(app.tasks):3d} tasks) "
              f"engine {mk['engine']:8.2f}  heft {mk['heft']:8.2f}  "
              f"etf {mk['etf']:8.2f}  ga {mk['ga']:8.2f} "
              f"({gain:+5.2f}%)  [{ga_s:5.2f}s]")
    mean_gain = float(np.mean([r["ga_gain_pct"] for r in rows]))
    print(f"{name:>8} mean GA gain over engine: {mean_gain:+.2f}%")
    return rows


# ---------------------------------------------------------------------------
def bench_phases(name: str, machine, params: SynthParams, pop_size: int,
                 seed: int, *, gens: int = 5,
                 min_speedup: float | None = None) -> dict:
    """Host per-generation phase breakdown vs one jitted device step."""
    import jax
    import jax.numpy as jnp

    from repro.search.device import generation_step, score_population
    from repro.search.ga import next_generation

    app = generate_app(params, seed)
    rng = np.random.default_rng(seed)
    n_tasks = len(app.tasks)
    pop = rng.integers(0, machine.n_cores, (pop_size, n_tasks),
                       dtype=np.int32)
    p_mut = max(1.0 / max(n_tasks, 1), 0.02)
    par = GAParams(pop_size=pop_size)

    t0 = time.perf_counter()
    schedules = decode_population(app, machine, pop)
    decode_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    batch = lower_population(app, machine, schedules)
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fit = simulate_batch(batch).t_exec
    fitness_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    next_generation(pop, fit, rng, par, p_mut=p_mut,
                    n_cores=machine.n_cores)
    select_s = time.perf_counter() - t0
    host_gen_s = decode_s + lower_s + fitness_s + select_s

    inp = device_inputs(app, machine)
    dpop = jnp.asarray(pop)
    dfit = score_population(inp, dpop)
    step = generation_step(par, n_tasks=n_tasks, n_cores=machine.n_cores)
    key = jax.random.PRNGKey(seed)
    step(inp, key, dpop, dfit)[1].fit.block_until_ready()  # jit warm-up
    t0 = time.perf_counter()
    p, f = dpop, dfit
    for i in range(gens):
        key, kg = jax.random.split(key)
        p, f = step(inp, kg, p, f)
    f.fit.block_until_ready()
    device_gen_s = (time.perf_counter() - t0) / gens

    row = {"suite": name, "pop": pop_size, "tasks": n_tasks,
           "subtasks": app.n_subtasks,
           "decode_s": round(decode_s, 4), "lower_s": round(lower_s, 4),
           "fitness_s": round(fitness_s, 4), "select_s": round(select_s, 4),
           "host_gen_s": round(host_gen_s, 4),
           "device_gen_s": round(device_gen_s, 5),
           "host_gens_per_s": round(1.0 / host_gen_s, 2),
           "device_gens_per_s": round(1.0 / device_gen_s, 2),
           "speedup": round(host_gen_s / device_gen_s, 2)}
    print(f"{name:>10} pop={pop_size:4d} host "
          f"{1e3 * host_gen_s:8.1f} ms/gen (decode {1e3 * decode_s:.1f} + "
          f"lower {1e3 * lower_s:.1f} + fitness {1e3 * fitness_s:.1f} + "
          f"select {1e3 * select_s:.1f})  device "
          f"{1e3 * device_gen_s:7.2f} ms/gen -> {row['speedup']:6.1f}x")
    if min_speedup is not None:
        assert row["speedup"] >= min_speedup, \
            f"device generation only {row['speedup']}x host on {name} " \
            f"(need >= {min_speedup}x)"
    return row


# ---------------------------------------------------------------------------
def bench_fitness(name: str, machine, params: SynthParams, pop_size: int,
                  seed: int) -> dict:
    """One population, two scoring paths — the GA's inner loop."""
    app = generate_app(params, seed)
    rng = np.random.default_rng(seed)
    pop = rng.integers(0, machine.n_cores, (pop_size, len(app.tasks)),
                       dtype=np.int32)
    schedules = decode_population(app, machine, pop)

    # equivalence gate before timing
    ref = [simulate_scenario(app, machine, s, contention=False).t_exec
           for s in schedules]
    got = simulate_batch(lower_population(app, machine, schedules)).t_exec
    np.testing.assert_allclose(ref, got, rtol=1e-9)

    t0 = time.perf_counter()
    for s in schedules:
        simulate_scenario(app, machine, s, contention=False)
    loop_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    simulate_batch(lower_population(app, machine, schedules))
    batch_s = time.perf_counter() - t0

    row = {"suite": name, "pop": pop_size, "tasks": len(app.tasks),
           "subtasks": app.n_subtasks,
           "loop_s": round(loop_s, 4), "batched_s": round(batch_s, 4),
           "loop_evals_per_s": round(pop_size / loop_s, 1),
           "batched_evals_per_s": round(pop_size / batch_s, 1),
           "speedup": round(loop_s / batch_s, 2)}
    print(f"{name:>8} pop={pop_size:3d} loop {1e3 * loop_s:8.1f} ms "
          f"({row['loop_evals_per_s']:8.1f} ev/s)  batched "
          f"{1e3 * batch_s:7.1f} ms ({row['batched_evals_per_s']:8.1f} ev/s) "
          f"-> {row['speedup']:5.1f}x")
    return row


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI-sized run")
    ap.add_argument("--json", default="BENCH_search.json")
    args = ap.parse_args()

    p8 = SynthParams(n_tasks=(15, 25))
    p64 = SynthParams(n_tasks=(120, 200))
    p256 = SynthParams(n_tasks=(240, 280))         # 1k+-subtask graphs
    m8 = dell_poweredge_1950()
    ga_par = GAParams(pop_size=16, generations=10, refine_rounds=2,
                      refine_moves=24, device=args.quick) \
        if args.quick else GAParams()

    print("== GA vs heuristics (elite-seeded: GA <= engine, asserted) ==")
    quality = bench_quality("8core", m8, p8,
                            n_apps=3 if args.quick else 10, seed=0,
                            ga_params=ga_par)
    if not args.quick:
        quality += bench_quality(
            "8core-dev", m8, p8, n_apps=10, seed=0,
            ga_params=GAParams(device=True))
        quality += bench_quality(
            "64core", hp_bl260c(), p64, n_apps=2, seed=100,
            ga_params=GAParams(pop_size=16, generations=8, refine_rounds=2,
                               refine_moves=32))
        quality += bench_quality(
            "64core-dev", hp_bl260c(), p64, n_apps=2, seed=100,
            ga_params=GAParams(pop_size=64, generations=16, refine_rounds=2,
                               refine_moves=64, device=True))
        quality += bench_quality(
            "256core-dev", cluster_of_multicores(32), p256, n_apps=2,
            seed=300,
            ga_params=GAParams(pop_size=64, generations=12, refine_rounds=1,
                               refine_moves=64, device=True))

    print("\n== per-generation phases: host decode/lower/fitness/select "
          "vs one jitted device step ==")
    if args.quick:
        phases = [bench_phases("8core", m8, p8, pop_size=32, seed=0,
                               gens=3)]
    else:
        phases = [bench_phases("8core", m8, p8, pop_size=256, seed=0,
                               min_speedup=5.0),
                  bench_phases("64core", hp_bl260c(), p64, pop_size=256,
                               seed=100),
                  bench_phases("256core", cluster_of_multicores(32), p256,
                               pop_size=256, seed=300)]

    print("\n== batched fitness vs per-candidate simulate_scenario loop ==")
    fitness = [bench_fitness("8core", m8, p8,
                             pop_size=32 if args.quick else 64, seed=0)]
    if not args.quick:
        fitness.append(bench_fitness("64core", hp_bl260c(), p64,
                                     pop_size=32, seed=100))

    out = Path(args.json)
    history = []
    if out.exists():
        try:
            history = json.loads(out.read_text())
        except json.JSONDecodeError:
            history = []
    history.append({"quick": args.quick, "quality": quality,
                    "phases": phases, "fitness": fitness})
    out.write_text(json.dumps(history, indent=1))
    print(f"\nwrote quality/phases/fitness sections -> {out}")


if __name__ == "__main__":
    main()
