"""jit'd public wrappers for the Pallas kernels.

Each wrapper compiles its kernel with Mosaic on a TPU and interprets it
anywhere else. Interpret mode exists for the CPU tests only: no chip run
may rely on it, and ``chip_smoke.py`` fails when a kernel's compiled
program holds no ``tpu_custom_call``.

The scheduling kernels (``sched_score`` / ``sim_relax_pop`` /
``sim_relax_pop_sweeps``) gather through caller-provided index arrays;
an out-of-bounds index does not crash on device, it clamps and reads
the wrong slot, returning a plausible wrong score. Their wrappers therefore run the tracer-safe checks from
:mod:`repro.analysis.ir_lint` before launch: shapes always (static
metadata even under ``jax.jit`` tracing — the device GA calls
``sim_relax_pop_sweeps`` inside its jitted generation step),
index-range checks whenever the operands are concrete."""

from __future__ import annotations

import jax

from ..analysis.ir_lint import check_gather_bounds, check_shape
from . import flash_attention as _fa
from . import flash_decode as _fd
from . import rmsnorm as _rn
from . import sched_score as _ss
from . import sim_step as _sim
from . import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, scale=None, window=None,
                    attn_softcap=None, q_block=512, kv_block=512):
    b, s, _, d = q.shape
    hkv = k.shape[2]
    check_shape("flash_attention.k", k, (b, s, hkv, d))
    check_shape("flash_attention.v", v, (b, s, hkv, v.shape[-1]))
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window, softcap=attn_softcap,
                               q_block=q_block, kv_block=kv_block,
                               interpret=not _on_tpu())


def rmsnorm(x, w, *, eps=1e-6, zero_centered=True):
    check_shape("rmsnorm.w", w, (x.shape[-1],))
    return _rn.rmsnorm(x, w, eps=eps, zero_centered=zero_centered,
                       interpret=not _on_tpu())


def ssd_scan(x, dt, A, B, C, chunk=256):
    b, s, h, _ = x.shape
    g, n = B.shape[2], B.shape[3]
    check_shape("ssd_scan.dt", dt, (b, s, h))
    check_shape("ssd_scan.A", A, (h,))
    check_shape("ssd_scan.B", B, (b, s, g, n))
    check_shape("ssd_scan.C", C, (b, s, g, n))
    return _ssd.ssd_scan(x, dt, A, B, C, chunk, interpret=not _on_tpu())


def sched_score(drain, frontiers, release, *, apps_block=128,
                cores_block=128):
    a, c = drain.shape
    check_shape("sched_score.frontiers", frontiers, (c,))
    check_shape("sched_score.release", release, (a,))
    return _ss.sched_score(drain, frontiers, release,
                           apps_block=apps_block, cores_block=cores_block,
                           interpret=not _on_tpu())


def _check_relax_pop(pred, lat, volbw, duration, release, name):
    b, s, p1 = pred.shape
    check_shape(f"{name}.lat", lat, (b, s, p1))
    check_shape(f"{name}.volbw", volbw, (b, s, p1))
    check_shape(f"{name}.duration", duration, (b, s))
    check_shape(f"{name}.release", release, (b, s))


def sim_relax_pop(pred, lat, volbw, duration, release, *, n_steps):
    _check_relax_pop(pred, lat, volbw, duration, release, "sim_relax_pop")
    # the kernel gathers end[pred] from an (S+1)-slot buffer whose last
    # slot is the zero sentinel; anything past it reads garbage
    check_gather_bounds(pred, pred.shape[1], "sim_relax_pop.pred")
    return _sim.sim_relax_pop(pred, lat, volbw, duration, release,
                              n_steps=n_steps, interpret=not _on_tpu())


def sim_relax_pop_sweeps(pred, lat, volbw, duration, release, *, n_steps):
    """``(ends, sweeps)``: :func:`sim_relax_pop` and the sweeps it ran."""
    _check_relax_pop(pred, lat, volbw, duration, release,
                     "sim_relax_pop_sweeps")
    check_gather_bounds(pred, pred.shape[1], "sim_relax_pop_sweeps.pred")
    return _sim.sim_relax_pop_sweeps(pred, lat, volbw, duration, release,
                                     n_steps=n_steps,
                                     interpret=not _on_tpu())


def flash_decode(q, k_cache, v_cache, pos, *, scale=None, softcap=None,
                 ring=False, kv_block=512):
    b, _, d = q.shape
    t, hkv = k_cache.shape[1], k_cache.shape[2]
    check_shape("flash_decode.k_cache", k_cache, (b, t, hkv, d))
    check_shape("flash_decode.v_cache", v_cache,
                (b, t, hkv, v_cache.shape[-1]))
    check_shape("flash_decode.pos", pos, (b,))
    if not ring:
        # pos counts valid cache entries, so [0, t]; past t the kernel
        # would mask against the wrong prefix and return plausible
        # garbage (ring buffers carry absolute positions — unbounded)
        check_gather_bounds(pos, t, "flash_decode.pos")
    return _fd.flash_decode(q, k_cache, v_cache, pos, scale=scale,
                            softcap=softcap, ring=ring, kv_block=kv_block,
                            interpret=not _on_tpu())
