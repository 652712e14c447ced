"""Batched simulation-relaxation Pallas kernel.

One synchronous sweep of the analytic execution recurrence over a whole
suite of lowered scenarios (``repro.core.lowering.dense_lags`` builds
the inputs):

    end'[b, s] = duration[b, s]
               + max(release[b, s], 0,
                     max_j (end[b, j] + lat[b, s, j]) + volbw[b, s, j])

``lat``/``volbw`` are dense ``(B, S, S)`` lag tensors, ``-inf`` where
subtask ``j`` does not gate subtask ``s`` (dependency edges carry the
comm latency and ``vol / bw``; the in-order core edge carries 0; the 0
floor stands in for an idle core). The two-add shape ``(end + lat) +
volbw`` matches the event simulator's ``now + latency + vol/bandwidth``
expression, so the float paths agree term by term.

The max-plus form is deliberately kernel-friendly: per grid cell one
VMEM tile of each lag tensor, a broadcast row of the current ends, an
elementwise add-add-max reduction along the lane axis — no gathers, no
cross-tile reductions. ``sim_relax`` iterates the step to the batch's
fixpoint depth under one ``jit``; the population variant
``sim_relax_pop`` stops its sweeps at the fixpoint itself, with
``n_steps`` as the bound. The NumPy oracle ``sim_step_np`` is
the allclose target (re-exported as ``kernels.ref.sim_step_ref``); the
float64 production path on CPU is the padded-CSR relaxation in
``repro.core.sim_engine.relax_batch_np`` — tests sweep all three
against each other.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def sim_step_np(end, lat, volbw, duration, release) -> np.ndarray:
    """NumPy oracle for one dense relaxation sweep (dtype-preserving).

    ``end`` (B, S); ``lat``/``volbw`` (B, S, S) with ``-inf`` non-edges;
    ``duration``/``release`` (B, S)."""
    end = np.asarray(end)
    ready = ((end[:, None, :] + np.asarray(lat))
             + np.asarray(volbw)).max(axis=-1, initial=-np.inf)
    zero = end.dtype.type(0.0)
    return np.asarray(duration) + np.maximum(np.asarray(release),
                                             np.maximum(ready, zero))


def _step_kernel(end_ref, lat_ref, volbw_ref, dur_ref, rel_ref, o_ref):
    end = end_ref[...]                        # (1, 1, S)
    ready = jnp.max((end + lat_ref[...]) + volbw_ref[...], axis=-1)
    o_ref[...] = dur_ref[...] + jnp.maximum(rel_ref[...],
                                            jnp.maximum(ready, 0.0))


def _pad_axis(x, axis: int, pad: int, value: float):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _prepare(lat, volbw, duration, release, sub_block: int,
             interpret: bool):
    """Shared cast/pad/pallas_call setup: returns a one-sweep step
    callable over padded ``(B, Sp)`` ends plus the (batch, valid,
    padded) sizes — sim_step and sim_relax must never drift apart."""
    lat = jnp.asarray(lat, jnp.float32)
    volbw = jnp.asarray(volbw, jnp.float32)
    duration = jnp.asarray(duration, jnp.float32)
    release = jnp.asarray(release, jnp.float32)
    b, s, _ = lat.shape
    sp = max(sub_block, ((s + 127) // 128) * 128)
    sb = min(sub_block, sp)
    pad = sp - s
    lat = _pad_axis(_pad_axis(lat, 1, pad, -jnp.inf), 2, pad, -jnp.inf)
    volbw = _pad_axis(_pad_axis(volbw, 1, pad, -jnp.inf), 2, pad, -jnp.inf)
    duration = _pad_axis(duration, 1, pad, 0.0)
    release = _pad_axis(release, 1, pad, 0.0)

    call = pl.pallas_call(
        _step_kernel,
        grid=(b, sp // sb),
        in_specs=[pl.BlockSpec((1, 1, sp), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, sb, sp), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, sb, sp), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, sb), lambda i, j: (i, j)),
                  pl.BlockSpec((1, sb), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, sb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, sp), jnp.float32),
        interpret=interpret,
    )

    def step(end):
        return call(end[:, None, :], lat, volbw, duration, release)

    return step, b, s, sp


@functools.partial(jax.jit, static_argnames=("n_steps", "sub_block",
                                             "interpret"))
def sim_relax(lat, volbw, duration, release, *, n_steps: int,
              sub_block: int = 128, interpret: bool = False):
    """Iterate the relaxation step ``n_steps`` times from all-zero ends.

    ``n_steps`` is the longest path of the scenario dependency graphs
    (``ScenarioBatch.depth``) — after that many sweeps every finish
    time is final. Returns (B, S) float32 ends.
    """
    step, b, s, sp = _prepare(lat, volbw, duration, release, sub_block,
                              interpret)
    end = jax.lax.fori_loop(0, n_steps, lambda _, e: step(e),
                            jnp.zeros((b, sp), jnp.float32))
    return end[:, :s]


@functools.partial(jax.jit, static_argnames=("sub_block", "interpret"))
def sim_step(end, lat, volbw, duration, release, *, sub_block: int = 128,
             interpret: bool = False):
    """One relaxation sweep (the oracle-shaped entry point)."""
    step, _, s, sp = _prepare(lat, volbw, duration, release, sub_block,
                              interpret)
    end = _pad_axis(jnp.asarray(end, jnp.float32), 1, sp - s, 0.0)
    return step(end)[:, :s]


# ---------------------------------------------------------------------------
# population-axis variant: sparse predecessor gathers instead of dense
# (B, S, S) lag tensors — O(B·S·P) memory, the shape a device-resident
# GA population (repro.search.device) and large ScenarioBatches need.
# ---------------------------------------------------------------------------

def pop_step_np(end, pred, lat, volbw, duration, release) -> np.ndarray:
    """NumPy oracle for one sparse population sweep (dtype-preserving).

    ``end`` (B, E) finish times with every sentinel slot holding 0;
    ``pred`` (B, S, P) int gather sources into the E axis (pads point at
    a sentinel slot); ``lat``/``volbw`` (B, S, P) with ``-inf`` pads;
    ``duration``/``release`` (B, S). The two-add shape ``(end + lat) +
    volbw`` matches the dense kernel and the event simulator."""
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
