"""Pure-jnp oracles for every Pallas kernel (the allclose targets of the
per-kernel shape/dtype sweeps in tests/test_kernels.py). The scheduler
scoring oracle is pure NumPy and lives in the JAX-free ``sched_ref``
module so the admission policies can use it without touching JAX; it is
re-exported here to keep one oracle registry."""

from __future__ import annotations

import jax.numpy as jnp

from repro.models.layers import attention_streamed, attention_windowed, rms_norm
from repro.models.ssm import ssd_chunked, ssd_sequential


def flash_attention_ref(q, k, v, *, causal=True, scale=None, window=None,
                        softcap=None):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if window is not None and causal:
        return attention_windowed(q, k, v, window=window, scale=scale,
                                  attn_softcap=softcap)
    return attention_streamed(q, k, v, causal=causal, scale=scale,
                              attn_softcap=softcap)


def rmsnorm_ref(x, w, *, eps=1e-6, zero_centered=True):
    return rms_norm(x, w, eps=eps, zero_centered=zero_centered)


def ssd_scan_ref(x, dt, A, B, C, chunk=256):
    return ssd_chunked(x, dt, A, B, C, chunk)


ssd_sequential_ref = ssd_sequential


from .sched_ref import sched_score_np as sched_score_ref  # noqa: E402
from .sim_step import pop_relax_np as sim_relax_pop_ref  # noqa: E402
from .sim_step import pop_step_np as sim_pop_step_ref  # noqa: E402


def decode_attention_ref(q, k_cache, v_cache, pos, *, scale=None,
                         softcap=None, ring=False):
    """q (B,Hq,D) vs (B,T,Hkv,D[v]) with ``pos`` valid entries."""
    import jax.numpy as jnp
    from repro.models.blocks import _decode_attn
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    t = k_cache.shape[1]
    idx = jnp.arange(t)
    limit = jnp.minimum(pos + 1, t) if ring else pos + 1
    valid = idx[None, :] < limit[:, None]
    out = _decode_attn(q[:, None], k_cache, v_cache, valid, scale, softcap)
    return out[:, 0]
