"""Distribution-layer tests. Anything needing >1 device runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 so the
main pytest process keeps seeing 1 device (per the dry-run contract)."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ARCHS, SHAPES, reduced
from repro.sharding.partition import MeshAxes


def run_sub(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=540)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_partition_specs_divisibility():
    """Every generated spec's sharded dims divide the mesh axis size —
    checked abstractly (no devices needed) for all 10 archs on a
    simulated 16x16 mesh via AbstractMesh."""
    from repro.launch.specs import abstract_params
    from repro.sharding.partition import Partitioner
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    sizes = {"data": 16, "model": 16}
    for name, cfg in ARCHS.items():
        part = Partitioner(mesh, MeshAxes(("data",), "model",
                                          fsdp=(cfg.name.startswith("qwen3"))))
        params = abstract_params(cfg)
        specs = part.param_specs(params)

        def walk(p_tree, s_tree):
            if isinstance(p_tree, dict):
                for k in p_tree:
                    walk(p_tree[k], s_tree[k])
            elif isinstance(p_tree, (list, tuple)):
                for a, b in zip(p_tree, s_tree):
                    walk(a, b)
            else:
                for dim, ax in zip(p_tree.shape, tuple(s_tree) + (None,) * 9):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    n = 1
                    for a in axes:
                        n *= sizes[a]
                    assert dim % n == 0, (name, p_tree.shape, s_tree)
        walk(params, specs)


def test_moe_a2a_matches_dense():
    """The production all_to_all EP dispatch == the dense oracle (same
    routing, generous capacity) on a real 8-device mesh."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models.moe import moe_a2a, moe_dense
        mesh = make_mesh((2, 4), ("data", "model"))
        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
        T, D, E, F, k = 64, 16, 8, 32, 2
        x = jax.random.normal(k1, (4, 16, D), jnp.float32)   # (B,S,D)
        params = {
            "router": jax.random.normal(k2, (D, E)) * 0.5,
            "wi": jax.random.normal(k3, (E, D, 2, F)) / np.sqrt(D),
            "wo": jax.random.normal(k4, (E, F, D)) / np.sqrt(F),
        }
        y_ref, aux_ref = moe_dense(x, params, k, "swiglu")
        with mesh:
            y, aux = jax.jit(lambda x, p: moe_a2a(
                x, p, top_k=k, activation="swiglu", n_experts=E,
                capacity_factor=8.0, mesh=mesh, dp_axes=("data",),
                ep_axis="model"))(x, params)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5, rtol=2e-5)
        # aux is computed per token-shard then averaged (standard for EP);
        # it is near but not equal to the global statistic
        assert abs(float(aux) - float(aux_ref)) < 0.5, (aux, aux_ref)
        print("A2A OK")
    """)


def test_moe_local_decode_matches_dense():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models.moe import moe_local_decode, moe_dense
        mesh = make_mesh((2, 4), ("data", "model"))
        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(1), 4)
        D, E, F, k = 16, 8, 32, 2
        x = jax.random.normal(k1, (4, 1, D), jnp.float32)
        params = {
            "router": jax.random.normal(k2, (D, E)) * 0.5,
            "wi": jax.random.normal(k3, (E, D, 2, F)) / np.sqrt(D),
            "wo": jax.random.normal(k4, (E, F, D)) / np.sqrt(F),
        }
        y_ref, _ = moe_dense(x, params, k, "swiglu")
        with mesh:
            y, _ = jax.jit(lambda x, p: moe_local_decode(
                x, p, top_k=k, activation="swiglu", n_experts=E,
                mesh=mesh, dp_axes=("data",), ep_axis="model"))(x, params)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-5, rtol=2e-5)
        print("LOCAL OK")
    """)


def test_sharded_train_step_matches_single_device():
    """One train step on the (2,4) mesh == the same step on 1 device
    (sharding must not change the math)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from dataclasses import replace
        from repro.configs import ARCHS, SHAPES, reduced
        from repro.launch.mesh import make_mesh
        from repro.launch import specs as S
        from repro.sharding.partition import Partitioner, MeshAxes
        from repro.optim.adamw import OptConfig
        from repro.runtime.train_loop import make_train_step, init_train_state
        from repro.models.model import ShardCtx

        cfg = reduced(ARCHS["glm4-9b"]).replace(dtype="float32")
        opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        key = jax.random.PRNGKey(0)
        state = init_train_state(cfg, opt, key)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}

        # single device
        s1, m1 = jax.jit(make_train_step(cfg, opt, ShardCtx()))(state, batch)

        # sharded
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = replace(SHAPES["train_4k"], seq_len=32, global_batch=8)
        axes = MeshAxes(("data",), "model")
        part = Partitioner(mesh, axes)
        ctx = S.make_ctx(cfg, shape, mesh, axes)
        pspecs = part.param_specs(state["params"])
        with mesh:
            step = make_train_step(cfg, opt, ctx, param_specs=pspecs)
            s2, m2 = jax.jit(step)(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-4, \
            (float(m1["loss"]), float(m2["loss"]))
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-3)
        print("PARITY OK")
    """)


def test_hlo_analyzer_counts_scan_bodies():
    """Trip-count correction: parsed dot FLOPs of a scanned matmul chain
    == analytic (XLA's own cost_analysis undercounts by the trip count)."""
    import jax.numpy as jnp
    from repro.launch.hlo_analysis import analyze_module
    w = jnp.zeros((128, 128), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=9)
        return y
    compiled = jax.jit(f).lower(jnp.zeros((128, 128))).compile()
    cost = analyze_module(compiled.as_text())
    assert abs(cost.dot_flops / (2 * 128 ** 3 * 9) - 1.0) < 1e-6
    assert cost.unknown_trip_counts == 0
