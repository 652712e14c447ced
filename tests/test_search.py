"""Mapping-search (GA + hill climber) tests: registry reachability,
determinism, the elite-seeding invariant (GA <= engine everywhere),
decoded-schedule validity for arbitrary gene vectors, batched fitness
== per-candidate event-simulator loop, and the device-resident GA
(``GAParams(device=True)``): fitness bit-for-bit against the
population-kernel NumPy oracle, equivalence with the host append-only
decode, fixed-seed determinism under jit, and the invariant on 64- and
256-core machines."""

import numpy as np
import pytest

from repro.core import (SCHEDULERS, SynthParams, cluster_of_multicores,
                        dell_poweredge_1950, generate_app, get_scheduler,
                        heterogeneous_cluster, hp_bl260c, lower_population,
                        simulate_batch, simulate_scenario, validate)
from repro.search import (GAParams, decode, decode_population, device_inputs,
                          encode, ga_schedule, ga_search, population_fitness,
                          population_fitness_device)

FAST = GAParams(pop_size=12, generations=6, refine_rounds=1, refine_moves=12)


def _app(seed, n_types=1):
    return generate_app(SynthParams(n_tasks=(10, 16), n_types=n_types), seed)


# ---------------------------------------------------------------------------
def test_registry_has_ga():
    assert "ga" in SCHEDULERS
    assert SCHEDULERS["ga"].task_coherent
    sched = get_scheduler("ga")(_app(0), dell_poweredge_1950(),
                                params=FAST)
    assert sched.makespan() > 0.0


def test_ga_deterministic_under_seed():
    app, m = _app(1), dell_poweredge_1950()
    a = ga_schedule(app, m, seed=7, params=FAST)
    b = ga_schedule(app, m, seed=7, params=FAST)
    assert {s: (p.core, p.start, p.end) for s, p in a.placements.items()} \
        == {s: (p.core, p.start, p.end) for s, p in b.placements.items()}


@pytest.mark.parametrize("machine_fn,n_types",
                         [(dell_poweredge_1950, 1),
                          (heterogeneous_cluster, 2)])
@pytest.mark.parametrize("seed", [0, 3, 11])
def test_elite_seeding_invariant_and_validity(machine_fn, n_types, seed):
    """GA makespan <= engine makespan on every scenario, and the result
    is a valid task-coherent schedule."""
    m = machine_fn()
    app = _app(seed, n_types=min(n_types, m.n_types))
    eng = get_scheduler("engine")(app, m)
    ga = ga_schedule(app, m, seed=0, params=FAST)
    validate(ga, app, m, require_task_coherence=True)
    assert ga.makespan() <= eng.makespan() + 1e-9


def test_decode_valid_for_arbitrary_vectors():
    """Any gene vector decodes to a precedence-correct, task-coherent,
    non-overlapping schedule — the no-repair property the GA relies on."""
    app, m = _app(5), dell_poweredge_1950()
    rng = np.random.default_rng(0)
    for _ in range(5):
        vec = rng.integers(0, m.n_cores, len(app.tasks))
        sch = decode(app, m, vec)
        validate(sch, app, m, require_task_coherence=True)
        got = encode(app, sch)
        assert np.array_equal(got, np.asarray(vec, np.int32))


def test_batched_fitness_matches_percandidate_loop():
    """The GA's one-call objective == looping simulate_scenario
    (analytic semantics) over every decoded candidate."""
    app, m = _app(2), dell_poweredge_1950()
    rng = np.random.default_rng(1)
    pop = rng.integers(0, m.n_cores, (16, len(app.tasks)), dtype=np.int32)
    batched = population_fitness(app, m, pop)
    loop = [simulate_scenario(app, m, s, contention=False).t_exec
            for s in decode_population(app, m, pop)]
    np.testing.assert_allclose(batched, loop, rtol=1e-9)


def test_ga_search_improves_or_matches_random_start():
    """Search fitness is monotone vs the best of its own first
    generation (elitism can only improve the best individual)."""
    app, m = _app(4), dell_poweredge_1950()
    rng = np.random.default_rng(9)
    first = rng.integers(0, m.n_cores, (FAST.pop_size, len(app.tasks)),
                         dtype=np.int32)
    # same seed => ga_search draws this exact initial population
    init_best = float(population_fitness(app, m, first).min())
    _, val = ga_search(app, m, seed=9, params=FAST)
    assert val <= init_best + 1e-9


def test_ga_schedule_respects_release_floors():
    """With a releases dict, every returned placement honors the floors
    — including when the heuristic fallback wins (it is re-decoded
    under the floors rather than returned verbatim)."""
    app, m = _app(6), dell_poweredge_1950()
    floors = {s: 25.0 for s in range(app.n_subtasks)}
    sch = ga_schedule(app, m, seed=0, params=FAST, releases=floors)
    validate(sch, app, m, require_task_coherence=True)
    assert min(p.start for p in sch.placements.values()) >= 25.0 - 1e-9


def test_online_ga_refine_keeps_validity_and_never_hurts():
    from repro.online import AppArrival, OnlineAMTHA

    m = dell_poweredge_1950()
    arrivals = [AppArrival(app_id=i, t_arrival=0.0, graph=_app(20 + i),
                           deadline=1e9, size_class="small")
                for i in range(3)]
    base = OnlineAMTHA(m)
    for a in arrivals:
        base.admit(a, at=0.0)
    refined = OnlineAMTHA(m, ga_refine=True, ga_params=FAST)
    for a in arrivals:
        refined.admit(a, at=0.0)
    refined.state.validate()
    assert refined.state.schedule.makespan() \
        <= base.state.schedule.makespan() + 1e-9


# ---------------------------------------------------------------------------
# device-resident GA (search/device.py)
# ---------------------------------------------------------------------------

FAST_DEV = GAParams(pop_size=12, generations=6, refine_rounds=1,
                    refine_moves=12, device=True)


def _pop(app, m, b=16, seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, m.n_cores, (b, len(app.tasks)), dtype=np.int32)


@pytest.mark.parametrize("method", ["scan", "kernel"])
def test_device_fitness_matches_pop_kernel_oracle_bitforbit(method):
    """Both device fitness paths (fused scan, population-axis Pallas
    kernel) reproduce the iterated NumPy oracle ``pop_relax_np`` exactly
    — same gathers, same f32 two-add expressions, contention-free."""
    import jax.numpy as jnp

    from repro.kernels.ref import sim_relax_pop_ref
    from repro.search.device import population_gather_inputs

    app, m = _app(2), dell_poweredge_1950()
    pop = _pop(app, m)
    inp = device_inputs(app, m)
    fit = np.asarray(population_fitness_device(inp, jnp.asarray(pop),
                                               method=method))
    gathered = [np.asarray(x) for x in
                population_gather_inputs(inp, jnp.asarray(pop))]
    ends = sim_relax_pop_ref(*gathered, n_steps=inp.n_subtasks)
    np.testing.assert_array_equal(fit, ends.max(axis=1))


def test_device_ga_kernel_sweeps_counted_and_same_as_scan():
    """The kernel fitness stops its sweeps at the fixpoint: the search
    counts one ``relax.calls`` per fitness call (initial, one per
    generation, one per refine round) with the sweeps run below their S
    bound, and returns what the scan fitness returns for the same
    seed. The scan fitness counts no sweeps."""
    from repro import obs
    from repro.search.device import ga_search_device

    app, m = _app(1), dell_poweredge_1950()
    par = GAParams(pop_size=8, generations=3, refine_rounds=2,
                   refine_moves=6, device=True)
    obs.reset()
    vk, fk = ga_search_device(app, m, seed=5, params=par, method="kernel")
    c = obs.snapshot()["counters"]
    obs.reset()
    vs, fs = ga_search_device(app, m, seed=5, params=par, method="scan")
    assert not any(k.startswith("relax.") for k in obs.snapshot()["counters"])
    assert np.array_equal(vk, vs) and fk == fs
    assert c["relax.calls"] == 1 + par.generations + c["ga.refine_rounds"]
    assert c["relax.sweep_bound"] == \
        c["relax.calls"] * device_inputs(app, m).n_subtasks
    assert c["relax.calls"] <= c["relax.sweeps"] < c["relax.sweep_bound"]
    assert c["ga.step_traces"] == 1


def test_device_fitness_matches_host_appendonly_decode():
    """Device fitness == lowering + simulating the host append-only
    decode (``gap_fill=False``) of the same genes — the device decoder's
    host oracle, up to f32."""
    import jax.numpy as jnp

    for seed in (2, 7):
        app, m = _app(seed), dell_poweredge_1950()
        pop = _pop(app, m, seed=seed)
        fit = np.asarray(population_fitness_device(
            device_inputs(app, m), jnp.asarray(pop)))
        scheds = decode_population(app, m, pop, gap_fill=False)
        host = simulate_batch(lower_population(app, m, scheds)).t_exec
        np.testing.assert_allclose(fit, host, rtol=1e-5, atol=1e-3)
        # append-only decodes are still valid schedules
        validate(scheds[0], app, m, require_task_coherence=True)


def test_device_fitness_respects_release_floors():
    import jax.numpy as jnp

    app, m = _app(3), dell_poweredge_1950()
    floors = {s: 40.0 for s in range(app.n_subtasks)}
    pop = _pop(app, m, b=4, seed=3)
    fit = np.asarray(population_fitness_device(
        device_inputs(app, m, releases=floors), jnp.asarray(pop)))
    scheds = decode_population(app, m, pop, releases=floors, gap_fill=False)
    host = simulate_batch(lower_population(app, m, scheds,
                                           releases=floors)).t_exec
    np.testing.assert_allclose(fit, host, rtol=1e-5, atol=1e-3)
    assert fit.min() >= 40.0


def test_device_ga_deterministic_under_seed():
    """The jitted loop is driven by one threaded PRNG key: same seed,
    same winner, bit-for-bit — including the device hill-climb."""
    app, m = _app(1), dell_poweredge_1950()
    v1, f1 = ga_search(app, m, seed=7, params=FAST_DEV)
    v2, f2 = ga_search(app, m, seed=7, params=FAST_DEV)
    assert np.array_equal(v1, v2) and f1 == f2


def test_device_ga_improves_on_initial_population():
    import jax
    import jax.numpy as jnp

    app, m = _app(4), dell_poweredge_1950()
    # ga_search_device draws its initial population from split(key)[1]
    k0 = jax.random.split(jax.random.PRNGKey(9))[1]
    first = jax.random.randint(k0, (FAST_DEV.pop_size, len(app.tasks)),
                               0, m.n_cores, jnp.int32)
    init_best = float(population_fitness_device(
        device_inputs(app, m), first).min())
    _, val = ga_search(app, m, seed=9, params=FAST_DEV)
    assert val <= init_best + 1e-6


@pytest.mark.parametrize("machine_fn,tasks", [
    (hp_bl260c, (40, 60)),                          # 64 cores
    (lambda: cluster_of_multicores(8), (60, 80)),   # 64 cores, 3-level comm
])
def test_device_ga_invariant_on_large_machines(machine_fn, tasks):
    """``ga <= engine`` survives the device routing on the big suites:
    the winner is re-decoded with the gap-filling host decoder and the
    result is never worse than the engine baseline."""
    m = machine_fn()
    app = generate_app(SynthParams(n_tasks=tasks), 31)
    eng = get_scheduler("engine")(app, m)
    par = GAParams(pop_size=12, generations=4, refine_rounds=1,
                   refine_moves=12, device=True)
    ga = ga_schedule(app, m, seed=0, params=par)
    validate(ga, app, m, require_task_coherence=True)
    assert ga.makespan() <= eng.makespan() + 1e-9


@pytest.mark.slow
def test_device_ga_invariant_on_256_core_cluster():
    m = cluster_of_multicores(32)                  # 256 cores
    app = generate_app(SynthParams(n_tasks=(120, 140)), 5)
    eng = get_scheduler("engine")(app, m)
    par = GAParams(pop_size=8, generations=3, refine_rounds=1,
                   refine_moves=8, device=True)
    ga = ga_schedule(app, m, seed=0, params=par)
    validate(ga, app, m, require_task_coherence=True)
    assert ga.makespan() <= eng.makespan() + 1e-9


def test_device_ga_respects_release_floors():
    app, m = _app(6), dell_poweredge_1950()
    floors = {s: 25.0 for s in range(app.n_subtasks)}
    sch = ga_schedule(app, m, seed=0, params=FAST_DEV, releases=floors)
    validate(sch, app, m, require_task_coherence=True)
    assert min(p.start for p in sch.placements.values()) >= 25.0 - 1e-9


@pytest.mark.parametrize("bad", [
    dict(pop_size=0), dict(elite=13), dict(elite=-1), dict(generations=0),
    dict(tournament=0), dict(elite_bias=1.5), dict(elite_bias=-0.1),
    dict(p_mutation=2.0), dict(refine_rounds=-1), dict(refine_moves=-1),
])
def test_gaparams_validated_on_construction(bad):
    with pytest.raises(ValueError):
        GAParams(pop_size=12, **bad) if "pop_size" not in bad \
            else GAParams(**bad)
