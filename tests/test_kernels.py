"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs the pure-jnp
oracles in repro.kernels.ref."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import (flash_attention_ref, rmsnorm_ref,
                               ssd_scan_ref, ssd_sequential_ref)

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def tol(dtype):
    return TOLS[jnp.bfloat16] if dtype == jnp.bfloat16 else TOLS[jnp.float32]


@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 2, 1, 32), (2, 256, 4, 2, 64), (1, 512, 8, 8, 16),
    (2, 128, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, hq, hkv, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("window", [16, 64, 200])
def test_flash_attention_windowed(window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 256, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              q_block=64, kv_block=64)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 4, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 4, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, attn_softcap=30.0,
                              q_block=64, kv_block=64)
    ref = flash_attention_ref(q, k, v, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("shape", [(7, 64), (4, 33, 128), (2, 8, 16, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(k1, shape, dtype)
    w = (jax.random.normal(k2, (shape[-1],), jnp.float32) * 0.1).astype(dtype)
    out = ops.rmsnorm(x, w)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 32, 16), (2, 128, 4, 32, 2, 16, 32),
    (1, 256, 8, 64, 1, 64, 64),
])
def test_ssd_scan_sweep(b, s, h, p, g, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y, st = ops.ssd_scan(x, dt, A, B, C, chunk)
    yr, sr = ssd_scan_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(sr, np.float32), atol=1e-4,
                               rtol=1e-4)


def test_ssd_chunked_matches_sequential():
    """The chunked dual form == the token-level recurrence (the kernel's
    oracle is itself verified against ground truth)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    b, s, h, p, g, n = 2, 96, 4, 8, 2, 16
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y1, f1 = ssd_scan_ref(x, dt, A, B, C, 32)
    y2, f2 = ssd_sequential_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("b,t,hq,hkv,d,ring", [
    (2, 256, 4, 2, 32, False), (1, 200, 8, 1, 64, False),
    (2, 128, 4, 4, 32, True),
])
def test_flash_decode_sweep(b, t, hq, hkv, d, ring):
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    pos = jax.random.randint(ks[3], (b,), 1, 2 * t if ring else t)
    from repro.kernels.ref import decode_attention_ref
    out = ops.flash_decode(q, kc, vc, pos, ring=ring, kv_block=64)
    ref = decode_attention_ref(q, kc, vc, pos, ring=ring)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_decode_softcap():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (2, 4, 32), jnp.float32)
    kc = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    vc = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    pos = jnp.asarray([60, 127])
    from repro.kernels.ref import decode_attention_ref
    out = ops.flash_decode(q, kc, vc, pos, softcap=30.0, kv_block=64)
    ref = decode_attention_ref(q, kc, vc, pos, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


# ---- sim_relax_pop: the sweeps stop at the fixpoint -----------------------

def _fixpoint_sweeps(pred, lat, volbw, duration, release, bound):
    """Sweeps the float32 oracle runs from zeros until one changes no bit
    (that sweep counted), at most ``bound``."""
    from repro.kernels.sim_step import pop_step_np
    b, s, _ = pred.shape
    end = np.zeros((b, s + 1), np.float32)
    for k in range(1, bound + 1):
        new = pop_step_np(end, pred, lat, volbw, duration, release)
        if np.array_equal(new.view(np.int32), end[:, :s].view(np.int32)):
            return k
        end[:, :s] = new
    return bound


def _random_population():
    """Eight random mappings of a small app on the 8-core machine, in the
    kernel's gather form."""
    from repro.core import SynthParams, dell_poweredge_1950, generate_app
    from repro.search.device import device_inputs, population_gather_inputs
    app, m = generate_app(SynthParams(n_tasks=(10, 16)), 2), \
        dell_poweredge_1950()
    inp = device_inputs(app, m)
    genes = np.random.default_rng(3).integers(0, m.n_cores,
                                              (8, len(app.tasks)),
                                              dtype=np.int32)
    return [np.asarray(x) for x in
            population_gather_inputs(inp, jnp.asarray(genes))]


def _chain(b=3, s=20):
    """One chain of ``s`` subtasks on one core: the dependency edge and
    the in-order edge both point at the previous subtask, so the last
    finish time is final only after ``s`` sweeps."""
    prev = np.arange(-1, s - 1)
    prev[0] = s                                   # the zero sentinel
    pred = np.broadcast_to(np.stack([prev, prev], axis=1), (b, s, 2))
    lag = np.where(pred < s, 0.0, -np.inf).astype(np.float32)
    duration = np.broadcast_to(np.linspace(1.0, 3.0, s, dtype=np.float32),
                               (b, s))
    return [np.ascontiguousarray(pred, np.int32), lag, lag.copy(),
            np.ascontiguousarray(duration),
            np.zeros((b, s), np.float32)]


@pytest.mark.parametrize("case", ["random", "chain", "below", "zero"])
def test_sim_relax_pop_stops_at_the_fixpoint(case):
    """Stopping at the first sweep that changes nothing returns the bits
    of all ``n_steps`` sweeps, and the count says where it stopped: before
    the bound on a random population, at the bound on a chain that needs
    every sweep, at ``n_steps`` when that is below the fixpoint, and no
    sweep at all for ``n_steps=0``."""
    from repro.kernels.sim_step import pop_relax_np
    args = _random_population() if case == "random" else _chain()
    s = args[0].shape[1]
    bound = {"random": s, "chain": s, "below": s // 2, "zero": 0}[case]
    want = pop_relax_np(*args, n_steps=bound)
    ends, sweeps = ops.sim_relax_pop_sweeps(*args, n_steps=bound)
    ends, sweeps = np.asarray(ends), int(sweeps)
    np.testing.assert_array_equal(ends.view(np.int32), want.view(np.int32))
    np.testing.assert_array_equal(
        np.asarray(ops.sim_relax_pop(*args, n_steps=bound)).view(np.int32),
        want.view(np.int32))
    assert sweeps == _fixpoint_sweeps(*args, bound)
    if case == "random":
        assert 0 < sweeps < bound
    else:
        assert sweeps == bound
    if case == "below":
        assert not np.array_equal(want, pop_relax_np(*args, n_steps=s))
    if case == "zero":
        assert not ends.any()
