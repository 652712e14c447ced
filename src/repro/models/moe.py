"""Mixture-of-Experts FFN with expert parallelism.

Three dispatch strategies, all computing the same math (top-k routing,
softmax-renormalized weights, dropped-token capacity model):

* ``dense``  — every expert on every token, masked combine. Used by the
  reduced smoke configs (single device, tiny dims) and as the oracle for
  the sharded paths.
* ``a2a``    — production EP for train/prefill: tokens are sharded over
  (data, model); a sort-based capacity dispatch builds per-destination
  buffers, ``all_to_all`` over the `model` axis moves tokens to their
  expert's owner, local expert GEMMs run, and the reverse ``all_to_all``
  returns them. This is the layer AMTHA's expert placement permutes
  (repro.core.placement.place_experts).
* ``local``  — decode: tokens replicated over `model` (batch is too small
  to split); each device runs only its local experts on all tokens and a
  ``psum`` over `model` combines. Latency-optimal at decode batch sizes.

The capacity model drops over-capacity tokens (standard "dropped" MoE) —
the combine weights renormalize over surviving experts.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import glu_mlp


def router_topk(x: jax.Array, w_router: jax.Array, top_k: int,
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (T, D); w_router (D, E) -> (weights (T,k), ids (T,k), aux_loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    e = w_router.shape[1]
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0) \
        / (ids.shape[0] * top_k)
    aux = e * jnp.sum(me * ce)
    return weights, ids, aux


def expert_ffn(xe: jax.Array, wi: jax.Array, wo: jax.Array,
               activation: str) -> jax.Array:
    """xe (E, C, D) tokens grouped per expert; wi (E, D, 2, F); wo (E, F, D)."""
    h = jnp.einsum("ecd,edxf->ecxf", xe, wi)
    gate, up = h[:, :, 0], h[:, :, 1]
    act = jax.nn.gelu(gate, approximate=True) if activation == "geglu" \
        else jax.nn.silu(gate)
    return jnp.einsum("ecf,efd->ecd", act * up, wo)


# ---------------------------------------------------------------------------
# dense (oracle / smoke)
# ---------------------------------------------------------------------------

def moe_dense(x: jax.Array, params: dict, top_k: int, activation: str
              ) -> tuple[jax.Array, jax.Array]:
    """x (..., D) -> (..., D). Computes all experts, masked combine."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    weights, ids, aux = router_topk(xt, params["router"], top_k)
    e = params["router"].shape[1]
    # combine weight per (token, expert)
    w_te = jnp.zeros((xt.shape[0], e), jnp.float32)
    w_te = w_te.at[jnp.arange(xt.shape[0])[:, None], ids].add(weights)
    ys = expert_ffn(jnp.broadcast_to(xt, (e,) + xt.shape),
                    params["wi"], params["wo"], activation)   # (E, T, D)
    y = jnp.einsum("etd,te->td", ys.astype(jnp.float32), w_te)
    return y.reshape(shape).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# sort-based capacity dispatch (shared by a2a path)
# ---------------------------------------------------------------------------

def _dispatch_indices(ids: jax.Array, top_k: int, n_experts: int,
                      capacity: int):
    """ids (T, k) -> (expert_sorted, token_sorted, slot, keep): for each of
    the T*k routed copies, its expert, source token, slot within the
    expert's capacity buffer, and whether it survived the capacity cut."""
    tk = ids.shape[0] * top_k
    flat_e = ids.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(ids.shape[0]), top_k)
    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    first = jnp.searchsorted(se, se, side="left")
    slot = jnp.arange(tk) - first
    keep = slot < capacity
    return se, st, slot, keep, order


# ---------------------------------------------------------------------------
# a2a path (train / prefill)
# ---------------------------------------------------------------------------

def moe_a2a_local(x_loc: jax.Array, params: dict, *, top_k: int,
                  activation: str, n_experts: int, capacity_factor: float,
                  axis: str) -> tuple[jax.Array, jax.Array]:
    """Body under shard_map. x_loc (T_loc, D) local tokens; params hold the
    *local* expert shard wi (E_loc, D, 2, F), wo (E_loc, F, D) and the
    replicated router (D, E)."""
    ep = jax.lax.psum(1, axis)                     # EP group size
    t_loc, d = x_loc.shape
    e_loc = params["wi"].shape[0]
    assert e_loc * ep == n_experts

    weights, ids, aux = router_topk(x_loc, params["router"], top_k)
    cap = max(1, int(t_loc * top_k / n_experts * capacity_factor))
    se, st, slot, keep, order = _dispatch_indices(ids, top_k, n_experts, cap)

    # send buffer (E, cap, D); dropped copies write into a junk row
    buf = jnp.zeros((n_experts, cap + 1, d), x_loc.dtype)
    buf = buf.at[se, jnp.where(keep, slot, cap)].set(x_loc[st])
    buf = buf[:, :cap]

    # (ep, E_loc, cap, D) -> a2a -> (ep, E_loc, cap, D) from each source
    send = buf.reshape(ep, e_loc, cap, d)
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    xe = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d)
    ye = expert_ffn(xe, params["wi"], params["wo"], activation)
    back = ye.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                             tiled=False)
    ybuf = ret.reshape(n_experts, cap, d)

    # combine: gather surviving copies back to their tokens
    flat_w = weights.reshape(-1)[order]
    y_copies = ybuf[se, jnp.clip(slot, 0, cap - 1)]
    y_copies = y_copies * (flat_w * keep)[:, None].astype(y_copies.dtype)
    y = jnp.zeros((t_loc, d), jnp.float32).at[st].add(
        y_copies.astype(jnp.float32))
    return y.astype(x_loc.dtype), aux


def moe_a2a(x: jax.Array, params: dict, *, top_k: int, activation: str,
            n_experts: int, capacity_factor: float, mesh: jax.sharding.Mesh,
            dp_axes: tuple[str, ...], ep_axis: str
            ) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) global. Tokens shard over (dp_axes..., ep_axis); expert
    weights shard over ep_axis."""
    b, s, d = x.shape

    def body(x_loc, router, wi, wo):
        bl, sl, _ = x_loc.shape
        y, aux = moe_a2a_local(
            x_loc.reshape(bl * sl, d), {"router": router, "wi": wi, "wo": wo},
            top_k=top_k, activation=activation, n_experts=n_experts,
            capacity_factor=capacity_factor, axis=ep_axis)
        # aux is per-shard; average over the whole mesh
        aux = jax.lax.pmean(aux, dp_axes + (ep_axis,))
        return y.reshape(bl, sl, d), aux

    spec_x = P(dp_axes, ep_axis, None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_x, P(), P(ep_axis, None, None, None),
                  P(ep_axis, None, None)),
        out_specs=(spec_x, P()))(
            x, params["router"], params["wi"], params["wo"])
    return out


# ---------------------------------------------------------------------------
# local path (decode)
# ---------------------------------------------------------------------------

def moe_local_decode(x: jax.Array, params: dict, *, top_k: int,
                     activation: str, n_experts: int,
                     mesh: jax.sharding.Mesh, dp_axes: tuple[str, ...],
                     ep_axis: str) -> tuple[jax.Array, jax.Array]:
    """x (B, 1, D): each device computes its local experts on all its
    tokens; psum over the EP axis combines. No a2a — decode batches are
    too small to split across the model axis."""
    b, s, d = x.shape

    def body(x_loc, router, wi, wo):
        bl = x_loc.shape[0]
        xt = x_loc.reshape(bl * s, d)
        weights, ids, aux = router_topk(xt, router, top_k)
        e_loc = wi.shape[0]
        ep_index = jax.lax.axis_index(ep_axis)
        # combine weight for *local* experts only
        w_te = jnp.zeros((xt.shape[0], n_experts), jnp.float32)
        w_te = w_te.at[jnp.arange(xt.shape[0])[:, None], ids].add(weights)
        w_local = jax.lax.dynamic_slice_in_dim(
            w_te, ep_index * e_loc, e_loc, axis=1)          # (T, E_loc)
        ys = expert_ffn(jnp.broadcast_to(xt, (e_loc,) + xt.shape), wi, wo,
                        activation)                          # (E_loc, T, D)
        y = jnp.einsum("etd,te->td", ys.astype(jnp.float32), w_local)
        y = jax.lax.psum(y, ep_axis)
        if dp_axes:        # aux is invariant over the EP axis (x replicated)
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(bl, s, d).astype(x_loc.dtype), aux

    spec_x = P(dp_axes, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_x, P(), P(ep_axis, None, None, None),
                  P(ep_axis, None, None)),
        out_specs=(spec_x, P()))(
            x, params["router"], params["wi"], params["wo"])


def moe_ffn(x: jax.Array, params: dict, cfg, ctx) -> tuple[jax.Array, jax.Array]:
    """Dispatch on the execution context (see model.ShardCtx)."""
    if ctx is None or ctx.mesh is None:
        return moe_dense(x, params, cfg.top_k, cfg.activation)
    if ctx.mode == "decode":
        return moe_local_decode(
            x, params, top_k=cfg.top_k, activation=cfg.activation,
            n_experts=cfg.n_experts, mesh=ctx.mesh, dp_axes=ctx.dp_axes,
            ep_axis=ctx.model_axis)
    return moe_a2a(
        x, params, top_k=cfg.top_k, activation=cfg.activation,
        n_experts=cfg.n_experts, capacity_factor=cfg.capacity_factor,
        mesh=ctx.mesh, dp_axes=ctx.dp_axes, ep_axis=ctx.model_axis)
