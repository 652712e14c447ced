"""Compile the main-path kernels for a described TPU v5e, with no chip.

Mosaic refuses what interpret mode accepts (blocks off the (8, 128)
tiling, in-kernel gathers), so these compiles guard the chip path on a
CPU-only machine. Each asserts that the compiled program holds the
kernel (``tpu_custom_call``), i.e. that it was not interpreted. The
topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import
this file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:              # no libtpu / no such topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip-targeted compile can be written to the persistent cache but
    # not read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _holds_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("p1", [9, 27])
def test_sim_relax_pop_compiles(one_chip, p1):
    from repro.kernels.sim_step import sim_relax_pop
    b, s = 256, 1090
    edge = [_spec(one_chip, (b, s, p1), dt)
            for dt in (jnp.int32, jnp.float32, jnp.float32)]
    node = [_spec(one_chip, (b, s), jnp.float32)] * 2
    compiled = sim_relax_pop.lower(*edge, *node, n_steps=s,
                                   interpret=False).compile()
    assert _holds_kernel(compiled)


def test_sim_relax_pop_compiles_at_the_join_width(one_chip):
    """A suite call of four Montage workflows (1223 jobs at most, 41 join
    rows) x 16 draws: rows of ROW_COLUMNS columns keep each grid cell's
    edge blocks in VMEM, where a 706-column padding does not fit."""
    from repro.core.lowering import ROW_COLUMNS
    from repro.kernels.sim_step import sim_relax_pop
    b, s = 64, 1264
    edge = [_spec(one_chip, (b, s, ROW_COLUMNS), dt)
            for dt in (jnp.int32, jnp.float32, jnp.float32)]
    node = [_spec(one_chip, (b, s), jnp.float32)] * 2
    compiled = sim_relax_pop.lower(*edge, *node, n_steps=29,
                                   interpret=False).compile()
    assert _holds_kernel(compiled)


@pytest.mark.parametrize("apps,cores", [(8, 256), (130, 300)])
def test_sched_score_compiles(one_chip, apps, cores):
    from repro.kernels.sched_score import sched_score
    compiled = sched_score.lower(
        _spec(one_chip, (apps, cores), jnp.float32),
        _spec(one_chip, (cores,), jnp.float32),
        _spec(one_chip, (apps,), jnp.float32),
        apps_block=128, cores_block=128, interpret=False).compile()
    assert _holds_kernel(compiled)


def test_generation_step_kernel_compiles(one_chip, monkeypatch):
    """The device-GA step with the Pallas fitness at the 256-core /
    1 090-subtask / pop-256 size. The kernel wrappers pick interpret
    mode from the backend, which is the CPU here: pretend it is a TPU
    so the kernel is really compiled."""
    from repro.core import (SynthParams, cluster_of_multicores,
                            generate_app)
    from repro.kernels import ops
    from repro.search import GAParams
    from repro.search.device import (Fitness, device_inputs,
                                     generation_step)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    machine = cluster_of_multicores(32)
    graph = generate_app(SynthParams(n_tasks=(240, 280)), seed=300)
    inp = device_inputs(graph, machine)
    assert (machine.n_cores, inp.n_subtasks) == (256, 1090)
    pop = 256
    params = GAParams(device=True, pop_size=pop)
    step = generation_step(params, n_tasks=len(graph.tasks),
                           n_cores=machine.n_cores, method="kernel")
    args = (jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), inp),
            _spec(one_chip, (2,), np.uint32),
            _spec(one_chip, (pop, len(graph.tasks)), jnp.int32),
            Fitness(_spec(one_chip, (pop,), jnp.float32),
                    _spec(one_chip, (), jnp.int32),
                    _spec(one_chip, (), jnp.int32)))
    assert _holds_kernel(step.lower(*args).compile())
