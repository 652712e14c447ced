"""Chip probe: the device GA's two fitness paths at one size.

    PYTHONPATH=src python -m benchmarks.fitness_timing [--seed N]

Times one fitness call of the Pallas kernel (``ops.sim_relax_pop``,
bounded at 64 sweeps and at the S sweeps the GA allows; the kernel
stops earlier at its fixpoint) and of the fused scan
(``search.device.population_ends``) on one random population of the
256-core / 1 090-subtask / pop-256 mapping search that ``chip_smoke.py``
drives, then checks that both give identical ends. Prints one JSON line
per measurement with its wall seconds per repeat (the first repeat
includes the compile). Exits non-zero without a TPU: an interpreted
kernel's time says nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def wall(fn, repeats: int) -> list[float]:
    import jax
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.core import SynthParams, cluster_of_multicores, generate_app
    from repro.kernels import ops
    from repro.search.device import (device_inputs, population_ends,
                                     population_gather_inputs)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"fitness_timing: no TPU found (JAX platform "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    machine = cluster_of_multicores(32)
    graph = generate_app(SynthParams(n_tasks=(240, 280)),
                         seed=300 + args.seed)
    inp = device_inputs(graph, machine)
    genes = jax.random.randint(jax.random.PRNGKey(args.seed),
                               (256, len(graph.tasks)), 0,
                               machine.n_cores, jnp.int32)
    gathered = jax.block_until_ready(
        jax.jit(population_gather_inputs)(inp, genes))
    n_sub = inp.n_subtasks
    print(json.dumps({"device": dev.device_kind, "cores": machine.n_cores,
                      "subtasks": n_sub, "pop": 256,
                      "p_plus_1": int(gathered[0].shape[2])}), flush=True)
    rows = {
        "kernel_64_sweeps": lambda: ops.sim_relax_pop(*gathered, n_steps=64),
        "kernel_S_sweeps": lambda: ops.sim_relax_pop(*gathered,
                                                     n_steps=n_sub),
        "scan": lambda: population_ends(inp, genes),
    }
    for name, fn in rows.items():
        print(json.dumps({name: wall(fn, 2)}), flush=True)
    a = np.asarray(rows["kernel_S_sweeps"]())
    b = np.asarray(rows["scan"]())
    print(json.dumps({"kernel_equals_scan": bool((a == b).all()),
                      "max_abs_diff": float(np.abs(a - b).max())}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
