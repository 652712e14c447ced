"""Simulation-engine benchmark: batched/array simulators vs seed loop.

    PYTHONPATH=src python -m benchmarks.sim_bench [--quick] [--json PATH]
                                                  [--pallas]

Two sections, both equivalence-checked while they time:

* **batched** — the paper-tables validation workload (schedule every
  app of a suite, then produce T_exec for all of them) under the
  analytic semantics: the per-scenario pure-Python event loop
  (``simulate(contention=False)`` once per app) against ONE
  ``simulate_suite`` call over the lowered scenario batch. Rows sweep
  the 8-core suite, a (suite × jitter-draws) scenario sweep, and (full
  run) the 64-core suite. The jitter=0 paths must agree to 1e-9
  relative or the row is refused.
* **events** — the exact contention+jitter path: the seed event loop
  against ``simulate_arrays`` (the same loop on the lowered IR), which
  must match **bit for bit** while it times.

``--pallas`` adds a correctness/timing smoke of the ``sim_relax_pop``
kernel path through ``simulate_batch`` (interpret mode off-TPU, so it is
a semantics check, not a speed claim). Results append to ``BENCH_sim.json`` so successive PRs
get a perf trajectory; CI runs ``--quick`` and uploads the file with
the other trajectory artifacts.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro.core import (SynthParams, batch_scenarios, dell_poweredge_1950,
                        generate_app, get_scheduler, hp_bl260c,
                        lower_scenario, repeat_batch, simulate,
                        simulate_arrays, simulate_batch, simulate_suite)


def _prepare(params: SynthParams, n_apps: int, seed: int, machine):
    schedule_fn = get_scheduler("engine")
    apps = [generate_app(params, seed + i) for i in range(n_apps)]
    schedules = [schedule_fn(g, machine) for g in apps]
    return apps, schedules


# ---------------------------------------------------------------------------
def bench_batched(name: str, machine, params: SynthParams, n_apps: int,
                  n_draws: int, seed: int) -> dict:
    """Suite validation: per-scenario Python loop vs one batched call."""
    apps, schedules = _prepare(params, n_apps, seed, machine)

    # equivalence gate (jitter=0): both paths must produce the same times
    loop0 = [simulate(g, machine, s, contention=False, jitter=0.0)
             for g, s in zip(apps, schedules)]
    batch0 = simulate_suite(apps, machine, schedules, jitter=0.0)
    np.testing.assert_allclose([r.t_exec for r in loop0], batch0.t_exec,
                               rtol=1e-9)

    # timed: the (apps × draws) jittered validation sweep
    t0 = time.perf_counter()
    for d in range(n_draws):
        for i, (g, s) in enumerate(zip(apps, schedules)):
            simulate(g, machine, s, contention=False, jitter=0.01,
                     seed=d * n_apps + i)
    loop_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    batch = repeat_batch(batch_scenarios(
        [lower_scenario(g, machine, s) for g, s in zip(apps, schedules)]),
        n_draws)
    res = simulate_batch(batch, jitter=0.01, seeds=range(batch.n_scenarios))
    batch_s = time.perf_counter() - t0

    row = {"suite": name, "apps": n_apps, "draws": n_draws,
           "scenarios": n_apps * n_draws,
           "subtasks": int(sum(g.n_subtasks for g in apps)),
           "loop_s": round(loop_s, 4), "batched_s": round(batch_s, 4),
           "speedup": round(loop_s / batch_s, 2),
           "mean_abs_dif_rel": round(float(np.abs(res.dif_rel()).mean()), 4)}
    print(f"{name:>12} apps={n_apps:3d} draws={n_draws} "
          f"loop {1e3 * loop_s:8.1f} ms  batched {1e3 * batch_s:7.1f} ms "
          f"-> {row['speedup']:6.1f}x")
    return row


# ---------------------------------------------------------------------------
def bench_events(name: str, machine, params: SynthParams, n_apps: int,
                 seed: int) -> dict:
    """Exact contention+jitter path: seed loop vs lowered event loop."""
    apps, schedules = _prepare(params, n_apps, seed, machine)

    t0 = time.perf_counter()
    ref = [simulate(g, machine, s, contention=True, jitter=0.01, seed=i)
           for i, (g, s) in enumerate(zip(apps, schedules))]
    seed_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    scenarios = [lower_scenario(g, machine, s)
                 for g, s in zip(apps, schedules)]
    got = [simulate_arrays(sa, contention=True, jitter=0.01, seed=i)
           for i, sa in enumerate(scenarios)]
    arrays_s = time.perf_counter() - t0

    for r, g in zip(ref, got):                # bit-for-bit or refuse the row
        if r.t_exec != g.t_exec or r.subtask_end != g.subtask_end:
            raise AssertionError(f"array event loop diverged on {name}")

    row = {"suite": name, "apps": n_apps,
           "subtasks": int(sum(g.n_subtasks for g in apps)),
           "seed_s": round(seed_s, 4), "arrays_s": round(arrays_s, 4),
           "speedup": round(seed_s / arrays_s, 2)}
    print(f"{name:>12} apps={n_apps:3d} contention+jitter "
          f"seed {1e3 * seed_s:8.1f} ms  arrays {1e3 * arrays_s:7.1f} ms "
          f"-> {row['speedup']:6.1f}x (bit-for-bit)")
    return row


# ---------------------------------------------------------------------------
def bench_pallas(machine, params: SynthParams, n_apps: int,
                 seed: int) -> dict:
    """sim_relax_pop kernel smoke: ``simulate_batch`` through Pallas
    (interpret mode off-TPU) vs the NumPy CSR path."""
    apps, schedules = _prepare(params, n_apps, seed, machine)
    scenarios = [lower_scenario(g, machine, s)
                 for g, s in zip(apps, schedules)]
    ref = simulate_batch(scenarios, jitter=0.0, backend="numpy")
    t0 = time.perf_counter()
    got = simulate_batch(scenarios, jitter=0.0, backend="pallas")
    pallas_s = time.perf_counter() - t0
    rel = np.abs(got.t_exec - ref.t_exec) / np.maximum(1.0, ref.t_exec)
    row = {"apps": n_apps, "pallas_s": round(pallas_s, 4),
           "max_rel_err": float(rel.max())}
    print(f"      pallas apps={n_apps:3d} {1e3 * pallas_s:8.1f} ms "
          f"max_rel_err={row['max_rel_err']:.2e} (float32 vs float64)")
    assert row["max_rel_err"] < 1e-5, "sim_relax_pop kernel diverged"
    return row


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="CI-sized run")
    ap.add_argument("--json", default="BENCH_sim.json")
    ap.add_argument("--pallas", action="store_true",
                    help="include the sim_relax_pop kernel smoke "
                    "(slow on CPU)")
    args = ap.parse_args()

    p8 = SynthParams(n_tasks=(15, 25))
    p64 = SynthParams(n_tasks=(120, 200))
    m8 = dell_poweredge_1950()

    print("== batched suite validation: per-scenario loop vs one call ==")
    batched = [bench_batched("8core", m8, p8, n_apps=6 if args.quick else 20,
                             n_draws=1, seed=0),
               bench_batched("8core-sweep", m8, p8,
                             n_apps=6 if args.quick else 20,
                             n_draws=4 if args.quick else 16, seed=0)]
    if not args.quick:
        batched.append(bench_batched("64core", hp_bl260c(), p64, n_apps=4,
                                     n_draws=1, seed=100))

    print("\n== exact event path: seed loop vs lowered loop ==")
    events = [bench_events("8core", m8, p8, n_apps=6 if args.quick else 20,
                           seed=0)]
    if not args.quick:
        events.append(bench_events("64core", hp_bl260c(), p64, n_apps=3,
                                   seed=100))

    pallas = []
    if args.pallas:
        print("\n== sim_relax_pop kernel (interpret off-TPU) ==")
        pallas.append(bench_pallas(m8, p8, n_apps=4, seed=0))

    out = Path(args.json)
    history = []
    if out.exists():
        try:
            history = json.loads(out.read_text())
        except json.JSONDecodeError:
            history = []
    history.append({"quick": args.quick, "batched": batched,
                    "events": events, "pallas": pallas})
    out.write_text(json.dumps(history, indent=1))
    print(f"\nwrote batched/events sections -> {out} "
          f"(every timed row equivalence-checked against the seed loop)")


if __name__ == "__main__":
    main()
