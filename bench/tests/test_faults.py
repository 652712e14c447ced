"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip, breaks one thing inside
the program that the window drives, and runs the rest of a run: set-up,
window and comparison. The faults are those each workload kind can have:
an answer altered where it is produced, half of a batch left out (the
rest standing in for it), and a step that leaves its state unchanged.
The suite runs at a tiny size; the search at the committed cell's own,
where its answer is the search's and not the engine's.
"""

import numpy as np


def _run(harness, cell):
    return harness.execute(cell, 11, 0.5, False)


# ---- mapping search -------------------------------------------------------
# at the committed cell's own graph and parameters, where the search beats
# the engine schedule it is seeded with

def test_search_state_unchanged(harness, search_cell, monkeypatch):
    import repro.search.device as device
    monkeypatch.setattr(device, "generation_step",
                        lambda *a, **k: lambda inp, key, pop, fit: (pop, fit))
    r = _run(harness, search_cell)
    assert not r["correct"] and r["checks"]["makespan_s"]["value"] > \
        r["checks"]["makespan_s"]["limit"]


def test_search_half_of_population_left_out(harness, search_cell,
                                            monkeypatch):
    import jax.numpy as jnp
    import repro.search.device as device
    fitness = device.population_fitness_device

    def half(inp, genes, **k):
        f = fitness(inp, genes, **k)
        n = f.shape[0] // 2
        # the unevaluated half reads as the best of the evaluated half
        return jnp.concatenate([f[:n], jnp.full(f.shape[0] - n,
                                                jnp.min(f[:n]) * 0.99)])
    monkeypatch.setattr(device, "population_fitness_device", half)
    assert not _run(harness, search_cell)["correct"]


def test_search_mapping_altered(harness, search_cell, monkeypatch):
    import repro.search.ga as ga
    decode = ga.decode

    def moved(graph, machine, vec, **k):
        vec = np.array(vec)
        vec[0] = (vec[0] + 1) % machine.n_cores
        tl = decode(graph, machine, vec, **k)
        p = tl.placements[0]
        tl.placements[0] = type(p)(p.sid, (p.core + 1) % machine.n_cores,
                                   p.start, p.end)
        return tl
    monkeypatch.setattr(ga, "decode", moved)
    monkeypatch.setattr(ga.Timeline, "makespan", lambda self: 0.0)
    assert not _run(harness, search_cell)["correct"]


# ---- suite validation -----------------------------------------------------

def test_suite_answer_altered(harness, tiny_cell, monkeypatch):
    from repro.kernels import ops
    relax = ops.sim_relax_pop
    monkeypatch.setattr(ops, "sim_relax_pop",
                        lambda *a, **k: relax(*a, **k).at[0].add(1.0))
    assert not _run(harness, tiny_cell("suite"))["correct"]


def test_suite_half_of_batch_left_out(harness, tiny_cell, monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import ops
    relax = ops.sim_relax_pop

    def half(pred, lat, volbw, duration, release, **k):
        n = pred.shape[0] // 2
        end = relax(pred[:n], lat[:n], volbw[:n], duration[:n],
                    release[:n], **k)
        fill = jnp.broadcast_to(end.mean(axis=0), (pred.shape[0] - n,
                                                   end.shape[1]))
        return jnp.concatenate([end, fill])
    monkeypatch.setattr(ops, "sim_relax_pop", half)
    assert not _run(harness, tiny_cell("suite"))["correct"]


def test_suite_state_unchanged(harness, tiny_cell, monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import ops
    monkeypatch.setattr(ops, "sim_relax_pop",
                        lambda pred, *a, **k: jnp.zeros(pred.shape[:2]))
    r = _run(harness, tiny_cell("suite"))
    assert not r["correct"] and r["checks"]["missing"]["value"] > 0
