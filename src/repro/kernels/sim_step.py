"""Batched simulation-relaxation Pallas kernel.

One synchronous sweep of the analytic execution recurrence over a batch
of lowered scenarios or a GA population, in the padded predecessor
gather form (``repro.core.lowering.relax_operands`` builds it for a
batch, ``repro.search.device.population_gather_inputs`` for a
population):

    end'[b, s] = duration[b, s]
               + max(release[b, s], 0,
                     max_j (end[b, pred[b, s, j]] + lat[b, s, j])
                     + volbw[b, s, j])

``pred``/``lat``/``volbw`` are ``(B, S, P+1)``: dependency edges carry
the comm latency and ``vol / bw``, the last column is the in-order core
edge with 0 lags, pads point at the always-zero sentinel slot ``S``
with ``-inf`` lags (the 0 floor stands in for an idle core). The
two-add shape ``(end + lat) + volbw`` matches the event simulator's
``now + latency + vol/bandwidth`` expression, so the float paths agree
term by term.

``sim_relax_pop`` iterates the sweep from zeros and stops at the
fixpoint, with ``n_steps`` as the bound. The NumPy oracles
``pop_step_np`` / ``pop_relax_np`` are its bit-for-bit targets; the
float64 production path on CPU is the padded-CSR relaxation in
``repro.core.sim_engine`` — tests sweep them against each other.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pad_axis(x, axis: int, pad: int, value: float):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pop_step_np(end, pred, lat, volbw, duration, release) -> np.ndarray:
    """NumPy oracle for one sparse population sweep (dtype-preserving).

    ``end`` (B, E) finish times with every sentinel slot holding 0;
    ``pred`` (B, S, P) int gather sources into the E axis (pads point at
    a sentinel slot); ``lat``/``volbw`` (B, S, P) with ``-inf`` pads;
    ``duration``/``release`` (B, S). The two-add shape ``(end + lat) +
    volbw`` matches the kernel and the event simulator."""
    end = np.asarray(end)
    b = end.shape[0]
    g = end[np.arange(b)[:, None, None], np.asarray(pred)]
    ready = ((g + np.asarray(lat)) + np.asarray(volbw)).max(axis=-1,
                                                            initial=-np.inf)
    zero = end.dtype.type(0.0)
    return np.asarray(duration) + np.maximum(np.asarray(release),
                                             np.maximum(ready, zero))


def pop_relax_np(pred, lat, volbw, duration, release, *,
                 n_steps: int) -> np.ndarray:
    """Iterated float32 oracle for :func:`sim_relax_pop` — bit-for-bit
    the kernel's result (same expressions, same f32 arithmetic).
    Sentinel convention: ``pred == S`` points at an always-zero slot."""
    pred = np.asarray(pred)
    b, s, _ = pred.shape
    lat = np.asarray(lat, np.float32)
    volbw = np.asarray(volbw, np.float32)
    duration = np.asarray(duration, np.float32)
    release = np.asarray(release, np.float32)
    end = np.zeros((b, s + 1), np.float32)
    for _ in range(n_steps):
        end[:, :s] = pop_step_np(end, pred, lat, volbw, duration, release)
    return np.array(end[:, :s])


_TILE = (8, 128)                      # one f32 vreg: (sublanes, lanes)


def _pop_step_kernel(end_ref, pred_ref, lat_ref, volbw_ref, dur_ref,
                     rel_ref, o_ref):
    """One sweep over an (8, 128) tile of (candidates, subtasks).

    ``end_ref`` holds the tile's 8 rows of current finish times, all
    Sp columns. Mosaic gathers only within one vreg, along the lanes,
    so each predecessor column is gathered 128 lanes at a time: chunk
    ``c`` of the rows answers the sources that fall in it."""
    lanes = _TILE[1]
    n_chunk = end_ref.shape[1] // lanes

    def edge(j, ready):
        idx = pred_ref[j]                                  # (8, 128)
        lane, chunk = idx & (lanes - 1), idx >> 7          # lanes = 2**7

        def pick(c, g):
            src = end_ref[:, pl.ds(pl.multiple_of(c * lanes, lanes), lanes)]
            return jnp.where(chunk == c,
                             jnp.take_along_axis(src, lane, axis=1), g)

        g = jax.lax.fori_loop(0, n_chunk, pick,
                              jnp.zeros(idx.shape, jnp.float32))
        return jnp.maximum(ready, (g + lat_ref[j]) + volbw_ref[j])

    ready = jax.lax.fori_loop(0, pred_ref.shape[0], edge,
                              jnp.full(o_ref.shape, -jnp.inf, jnp.float32))
    o_ref[...] = dur_ref[...] + jnp.maximum(rel_ref[...],
                                            jnp.maximum(ready, 0.0))


def _relax_pop(pred, lat, volbw, duration, release, n_steps: int,
               interpret: bool):
    """(ends, sweeps): the sweeps of :func:`sim_relax_pop` and how many
    ran. A sweep is a pure function of the end buffer, so the first
    sweep that leaves the whole padded buffer bitwise unchanged proves
    every later one would too: the loop stops there (that sweep
    counted) or after ``n_steps`` sweeps, whichever comes first, and
    returns bit-for-bit what ``n_steps`` sweeps return."""
    pred = jnp.asarray(pred, jnp.int32)
    lat = jnp.asarray(lat, jnp.float32)
    volbw = jnp.asarray(volbw, jnp.float32)
    duration = jnp.asarray(duration, jnp.float32)
    release = jnp.asarray(release, jnp.float32)
    b, s, p = pred.shape
    bb, sb = _TILE
    bp = -(-b // bb) * bb
    sp = -(-(s + 1) // sb) * sb

    def lay(x, value):                  # (B, S[, P]) -> padded (P, Bp, Sp)
        x = _pad_axis(_pad_axis(x, 0, bp - b, value), 1, sp - s, value)
        return jnp.moveaxis(x, 2, 0) if x.ndim == 3 else x

    pred, lat, volbw = lay(pred, s), lay(lat, -jnp.inf), lay(volbw, -jnp.inf)
    duration, release = lay(duration, 0.0), lay(release, 0.0)
    edge = pl.BlockSpec((p, bb, sb), lambda i, j: (0, i, j))
    node = pl.BlockSpec((bb, sb), lambda i, j: (i, j))
    call = pl.pallas_call(
        _pop_step_kernel,
        grid=(bp // bb, sp // sb),
        in_specs=[pl.BlockSpec((bb, sp), lambda i, j: (i, 0)),
                  edge, edge, edge, node, node],
        out_specs=node,
        out_shape=jax.ShapeDtypeStruct((bp, sp), jnp.float32),
        interpret=interpret,
    )

    def sweep(carry):
        end, _, k = carry
        new = call(end, pred, lat, volbw, duration, release)
        bits = functools.partial(jax.lax.bitcast_convert_type,
                                 new_dtype=jnp.int32)
        return new, jnp.any(bits(new) != bits(end)), k + 1

    end, _, sweeps = jax.lax.while_loop(
        lambda c: c[1] & (c[2] < n_steps), sweep,
        (jnp.zeros((bp, sp), jnp.float32), jnp.array(True),
         jnp.zeros((), jnp.int32)))
    return end[:b, :s], sweeps


@functools.partial(jax.jit, static_argnames=("n_steps", "interpret"))
def sim_relax_pop(pred, lat, volbw, duration, release, *, n_steps: int,
                  interpret: bool = False):
    """Iterate the sparse population sweep from zeros to its fixpoint,
    at most ``n_steps`` sweeps.

    Inputs are the padded-CSR gather form: ``pred`` (B, S, P) int32
    sources with sentinel ``S``, ``lat``/``volbw`` (B, S, P) per-edge
    lags (``-inf`` pads), ``duration``/``release`` (B, S). The padded
    end buffer keeps one extra 128-aligned region whose rows evaluate
    to exactly 0 every sweep (0 duration, 0 release, all-(-inf) lags),
    so the sentinel slot needs no special handling inside the kernel.
    Returns (B, S) float32 finish times, bit-for-bit those of
    ``n_steps`` sweeps: the loop stops at the first sweep that changes
    nothing. An acyclic batch gets there within its longest path plus
    one, so the longest path (or S) as ``n_steps`` is always enough.

    The per-edge operands are laid out (P, B, S), so every block is
    (P, 8, 128) or (8, 128): the TPU tiling. B is padded to a multiple
    of 8 with rows that stay 0."""
    return _relax_pop(pred, lat, volbw, duration, release, n_steps,
                      interpret)[0]


@functools.partial(jax.jit, static_argnames=("n_steps", "interpret"))
def sim_relax_pop_sweeps(pred, lat, volbw, duration, release, *,
                         n_steps: int, interpret: bool = False):
    """:func:`sim_relax_pop` with the number of sweeps it ran: returns
    ``(ends, sweeps)``, ``sweeps`` an int32 device scalar in
    ``[0, n_steps]`` that counts the last sweep, the one that changed
    nothing, unless the bound stopped the loop first."""
    return _relax_pop(pred, lat, volbw, duration, release, n_steps,
                      interpret)
