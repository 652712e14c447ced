"""Per-layer blocks: init + forward for every layer kind.

Kinds: ``dense_global`` / ``dense_local`` (attention + GLU MLP, optional
qk-norm / softcap / post-block norms), ``moe_global`` (attention + MoE
FFN + optional shared experts), ``ssm`` (Mamba-2), and the Zamba-2
``shared`` transformer block (weights reused across slots, per-slot LoRA).

Deepseek-style MLA replaces the attention projections when
``cfg.kv_lora_rank > 0`` — decode runs the *absorbed* form (scores in the
latent space, so the cache stays (T, kv_lora + rope) per token).

Every forward returns ``(x, aux_loss, new_cache)``; cache is None outside
decode/prefill. KV caches for ``dense_local`` layers are ring buffers of
length ``window`` (RoPE is applied at insert with absolute positions, so
slot order is irrelevant to attention).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import moe as moe_lib
from .layers import (NEG_INF, apply_rope, attention, glu_mlp, rms_norm,
                     softcap)
from .ssm import ssd_chunked, ssd_decode_step


def _init(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# attention sub-block (shared by dense/moe/encoder/vlm kinds)
# ---------------------------------------------------------------------------

def init_attention(cfg, key, d_in=None):
    d = d_in or cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    if cfg.kv_lora_rank:            # MLA
        dq = cfg.qk_nope_dim + cfg.qk_rope_dim
        p = {
            "wq": _init(ks[0], (d, cfg.n_heads, dq), d, dt),
            "wkv_a": _init(ks[1], (d, cfg.kv_lora_rank + cfg.qk_rope_dim), d, dt),
            "kv_norm": jnp.zeros((cfg.kv_lora_rank,), dt),
            "wkv_b": _init(ks[2], (cfg.kv_lora_rank,
                                   cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim),
                           cfg.kv_lora_rank, dt),
            "wo": _init(ks[3], (cfg.n_heads, cfg.v_head_dim, d),
                        cfg.n_heads * cfg.v_head_dim, dt),
        }
    else:
        p = {
            "wq": _init(ks[0], (d, cfg.n_heads, cfg.head_dim), d, dt),
            "wk": _init(ks[1], (d, cfg.n_kv_heads, cfg.head_dim), d, dt),
            "wv": _init(ks[2], (d, cfg.n_kv_heads, cfg.head_dim), d, dt),
            "wo": _init(ks[3], (cfg.n_heads, cfg.head_dim, d),
                        cfg.n_heads * cfg.head_dim, dt),
        }
    if cfg.qk_norm:
        dh = cfg.head_dim if not cfg.kv_lora_rank else cfg.qk_nope_dim + cfg.qk_rope_dim
        p["qnorm"] = jnp.zeros((dh,), dt)
        p["knorm"] = jnp.zeros((dh,), dt)
    return p


def _attn_activation_specs(ctx):
    """(qkv_spec, kv_spec) claiming the model axis for attention
    activations when attention weights are replicated (small-head archs).
    "batch": shard batch over (dp + model); "seq": shard q's sequence
    over model, keep K/V full (sequence-parallel attention)."""
    if ctx is None or ctx.mesh is None or \
            ctx.attn_mode not in ("batch", "seq"):
        return None, None
    from jax.sharding import PartitionSpec as P
    dp = tuple(ctx.dp_axes)
    if ctx.attn_mode == "batch":
        spec = P(dp + (ctx.model_axis,), None, None, None)
        return spec, spec
    q_spec = P(dp if dp else None, ctx.model_axis, None, None)
    kv_spec = P(dp if dp else None, None, None, None)
    return q_spec, kv_spec


def _shard_map_seq_attention(q, k, v, *, cfg, ctx, window, scale,
                             prefix_len=None):
    """Sequence-parallel attention under shard_map: each model-rank owns a
    contiguous S/model_n slice of the *queries* and sees the full K/V
    (already replicated over `model` — weights are replicated for these
    archs, so no gather is inserted). Removes the model_n× attention
    duplication of the replicated baseline without relying on GSPMD to
    reshard through the TP-MLP boundary (it can't — involuntary full
    remat). EXPERIMENTS.md §Perf quantifies the win."""
    from jax.sharding import PartitionSpec as P
    dp = tuple(ctx.dp_axes) or None
    ax = ctx.model_axis

    vma = tuple(ctx.dp_axes) + (ax,)
    kv_vma = tuple(ctx.dp_axes)

    def body(q_loc, k_full, v_full, prefix):
        off = jax.lax.axis_index(ax) * q_loc.shape[1]
        return attention(q_loc, k_full, v_full, causal=cfg.causal,
                         window=window, scale=scale,
                         attn_softcap=cfg.attn_softcap,
                         prefix_len=prefix if prefix_len is not None else None,
                         q_offset=off, vma_axes=vma, kv_vma_axes=kv_vma)

    prefix = prefix_len if prefix_len is not None else \
        jnp.zeros((q.shape[0],), jnp.int32)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dp, ax, None, None), P(dp, None, None, None),
                  P(dp, None, None, None), P(dp)),
        out_specs=P(dp, ax, None, None))(q, k, v, prefix)


def _constrain(t, spec):
    return t if spec is None else jax.lax.with_sharding_constraint(t, spec)


def _qkv(p, x, cfg, lora=None):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if lora is not None:
        def ad(i, name, t):
            return t + jnp.einsum("bsd,dr,rhk->bshk", x,
                                  lora["a"][i], lora[f"b_{name}"])
        q, k, v = ad(0, "q", q), ad(1, "k", k), ad(2, "v", v)
    return q, k, v


def attn_forward(p, x, *, cfg, kind, ctx, positions, cache=None,
                 prefix_len=None, lora=None):
    """Returns (attn_out (B,S,d), new_cache)."""
    local = kind.endswith("local")
    theta = cfg.rope_theta_local if local else cfg.rope_theta
    window = cfg.window if local else None
    mode = ctx.mode if ctx else "train"

    if cfg.kv_lora_rank:
        return _mla_forward(p, x, cfg=cfg, ctx=ctx, positions=positions,
                            cache=cache)

    q, k, v = _qkv(p, x, cfg, lora)
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"])
        k = rms_norm(k, p["knorm"])
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    scale = cfg.attn_scale or (q.shape[-1] ** -0.5)

    if mode == "decode":
        kc, vc, valid = _cache_insert(cache, k, v, positions, window)
        out = _decode_attn(q, kc, vc, valid, scale, cfg.attn_softcap)
        new_cache = {"k": kc, "v": vc}
    elif ctx is not None and ctx.attn_mode == "shard_map_seq" \
            and ctx.mesh is not None:
        out = _shard_map_seq_attention(q, k, v, cfg=cfg, ctx=ctx,
                                       window=window, scale=scale,
                                       prefix_len=prefix_len)
        new_cache = _prefill_cache(k, v, window) if mode == "prefill" else None
    else:
        q_spec, kv_spec = _attn_activation_specs(ctx)
        q = _constrain(q, q_spec)
        k, v = _constrain(k, kv_spec), _constrain(v, kv_spec)
        vma = ctx.vma_axes if ctx is not None else ()
        out = attention(q, k, v, causal=cfg.causal, window=window,
                        scale=scale, attn_softcap=cfg.attn_softcap,
                        prefix_len=prefix_len, backend=cfg.attn_backend,
                        vma_axes=vma, kv_vma_axes=vma)
        new_cache = _prefill_cache(k, v, window) if mode == "prefill" else None
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if mode != "decode" and ctx is not None and ctx.attn_mode is not None \
            and ctx.mesh is not None:
        # hand the residual back in its canonical (dp-only) sharding so the
        # attention-side batch/seq claim on `model` never leaks into the MLP
        from jax.sharding import PartitionSpec as P
        dp = tuple(ctx.dp_axes)
        out = _constrain(out, P(dp if dp else None, None, None))
    return out, new_cache


def _decode_attn(q, k_cache, v_cache, valid, scale, cap):
    """q (B,1,Hq,D) vs cache (B,T,Hkv,D); ``valid`` (B,T) bool."""
    b, _, hq, _ = q.shape
    hkv = k_cache.shape[2]
    qg = q.reshape(b, 1, hkv, hq // hkv, -1)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_cache) * scale
    scores = softcap(scores.astype(jnp.float32), cap)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_cache)
    return out.reshape(b, 1, hq, v_cache.shape[-1])


def _cache_insert(cache, k, v, positions, window):
    """Insert one token into a (ring when local) cache; return
    (k_cache, v_cache, valid_mask). ``positions`` is the scalar abs pos."""
    kc, vc = cache["k"], cache["v"]
    t = kc.shape[1]
    pos = jnp.asarray(positions).reshape(())      # scalar decode position
    slot = pos % t if window is not None else pos
    kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), slot, 1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), slot, 1)
    idx = jnp.arange(t)
    valid = (idx <= pos) if window is None else \
        (idx < jnp.minimum(pos + 1, t))
    return kc, vc, jnp.broadcast_to(valid[None], (k.shape[0], t))


def _prefill_cache(k, v, window):
    if window is not None and k.shape[1] > window:
        # ring layout: position p lives at slot p % window
        s = k.shape[1]
        keep = jnp.arange(s - window, s)
        slots = keep % window
        kc = jnp.zeros((k.shape[0], window) + k.shape[2:], k.dtype)
        vc = jnp.zeros_like(kc)
        kc = kc.at[:, slots].set(k[:, keep])
        vc = vc.at[:, slots].set(v[:, keep])
        return {"k": kc, "v": vc}
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (deepseek) — prefill materializes per-head K/V; decode is absorbed
# ---------------------------------------------------------------------------

def _mla_forward(p, x, *, cfg, ctx, positions, cache):
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rope_d, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    mode = ctx.mode if ctx else "train"

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv_a = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    latent = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)                       # (B,S,1,rope)
    scale = (nope + rope_d) ** -0.5

    if mode == "decode":
        # absorbed: q_eff = q_nope @ W_b^K -> latent space
        wb_k = p["wkv_b"][..., :nope]                         # (L, H, nope)
        wb_v = p["wkv_b"][..., nope:]                         # (L, H, v)
        q_eff = jnp.einsum("bshk,lhk->bshl", q_nope, wb_k)    # (B,1,H,L)
        lc, rc, valid = _mla_cache_insert(cache, latent, k_rope[:, :, 0, :],
                                          positions)
        qcat = jnp.concatenate([q_eff, q_rope], -1)           # (B,1,H,L+r)
        kcat = jnp.concatenate([lc, rc], -1)[:, :, None, :]   # (B,T,1,L+r)
        out_l = _decode_attn(qcat, kcat, lc[:, :, None, :], valid, scale, None)
        out = jnp.einsum("bshl,lhv->bshv", out_l, wb_v)       # (B,1,H,v)
        new_cache = {"latent": lc, "k_rope": rc}
    else:
        kv = jnp.einsum("bsl,lhk->bshk", latent, p["wkv_b"])
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope_d))], -1)
        qcat = jnp.concatenate([q_nope, q_rope], -1)
        out = attention(qcat, k, v, causal=cfg.causal, scale=scale,
                        backend=cfg.attn_backend)
        new_cache = {"latent": latent, "k_rope": k_rope[:, :, 0, :]} \
            if mode == "prefill" else None
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"]), new_cache


def _mla_cache_insert(cache, latent, k_rope, positions):
    lc, rc = cache["latent"], cache["k_rope"]
    pos = jnp.asarray(positions).reshape(())
    lc = jax.lax.dynamic_update_slice_in_dim(lc, latent.astype(lc.dtype), pos, 1)
    rc = jax.lax.dynamic_update_slice_in_dim(rc, k_rope.astype(rc.dtype), pos, 1)
    valid = jnp.arange(lc.shape[1]) <= pos
    return lc, rc, jnp.broadcast_to(valid[None], (latent.shape[0], lc.shape[1]))


# ---------------------------------------------------------------------------
# dense / moe transformer layers
# ---------------------------------------------------------------------------

def init_mlp(cfg, key, d_in=None):
    d = d_in or cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    k1, k2 = jax.random.split(key)
    cols = 2 if cfg.activation in ("geglu", "swiglu") else 1
    return {"wi": _init(k1, (d, cols, cfg.d_ff), d, dt),
            "wo": _init(k2, (cfg.d_ff, d), cfg.d_ff, dt)}


def init_layer(kind, cfg, key):
    dt = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return init_mamba(cfg, key)
    p = {"ln1": jnp.zeros((d,), dt), "ln2": jnp.zeros((d,), dt),
         "attn": init_attention(cfg, ks[0])}
    if cfg.post_block_norms:
        p["post_ln1"] = jnp.zeros((d,), dt)
        p["post_ln2"] = jnp.zeros((d,), dt)
    if kind.startswith("moe"):
        e, f = cfg.n_experts, cfg.d_ff_expert
        k1, k2, k3, k4 = jax.random.split(ks[1], 4)
        p["moe"] = {
            "router": _init(k1, (d, e), d, jnp.float32),
            "wi": _init(k2, (e, d, 2, f), d, dt),
            "wo": _init(k3, (e, f, d), f, dt),
        }
        if cfg.n_shared_experts:
            fs = cfg.d_ff_expert * cfg.n_shared_experts
            ka, kb = jax.random.split(k4)
            p["shared_mlp"] = {"wi": _init(ka, (d, 2, fs), d, dt),
                               "wo": _init(kb, (fs, d), fs, dt)}
    else:
        p["mlp"] = init_mlp(cfg, ks[1])
    return p


def layer_forward(kind, p, x, *, cfg, ctx, positions, cache=None,
                  prefix_len=None):
    """One transformer layer. Returns (x, aux, new_cache)."""
    if kind == "ssm":
        y, new_cache = mamba_forward(p, x, cfg=cfg, ctx=ctx, cache=cache)
        return x + y, jnp.zeros((), jnp.float32), new_cache

    h = rms_norm(x, p["ln1"])
    attn_out, new_cache = attn_forward(p["attn"], h, cfg=cfg, kind=kind,
                                       ctx=ctx, positions=positions,
                                       cache=cache, prefix_len=prefix_len)
    if cfg.post_block_norms:
        attn_out = rms_norm(attn_out, p["post_ln1"])
    x = x + attn_out

    h = rms_norm(x, p["ln2"])
    if kind.startswith("moe"):
        ff, aux = moe_lib.moe_ffn(h, p["moe"], cfg, ctx)
        if cfg.n_shared_experts:
            ff = ff + glu_mlp(h, p["shared_mlp"]["wi"], p["shared_mlp"]["wo"],
                              cfg.activation)
    else:
        ff = glu_mlp(h, p["mlp"]["wi"], p["mlp"]["wo"], cfg.activation)
        aux = jnp.zeros((), jnp.float32)
    if cfg.post_block_norms:
        ff = rms_norm(ff, p["post_ln2"])
    return x + ff, aux, new_cache


# ---------------------------------------------------------------------------
# mamba2 layer
# ---------------------------------------------------------------------------

def init_mamba(cfg, key):
    dt = jnp.dtype(cfg.dtype)
    d, di = cfg.d_model, cfg.d_inner
    g, n, h = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_heads
    k = jax.random.split(key, 8)
    return {
        "ln": jnp.zeros((d,), dt),
        "wz": _init(k[0], (d, di), d, dt),
        "wx": _init(k[1], (d, di), d, dt),
        "wB": _init(k[2], (d, g * n), d, dt),
        "wC": _init(k[3], (d, g * n), d, dt),
        "wdt": _init(k[4], (d, h), d, dt),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "conv_x": _init(k[5], (cfg.ssm_conv, di), cfg.ssm_conv, dt),
        "conv_B": _init(k[6], (cfg.ssm_conv, g * n), cfg.ssm_conv, dt),
        "conv_C": _init(k[7], (cfg.ssm_conv, g * n), cfg.ssm_conv, dt),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": jnp.zeros((di,), dt),
        "wout": _init(jax.random.fold_in(key, 9), (di, d), di, dt),
    }


def _causal_conv(x, w, cache=None):
    """Depthwise causal conv. x (B,S,C); w (K,C); cache (B,K-1,C) for
    decode (S=1). Returns (y, new_cache or None)."""
    k = w.shape[0]
    if cache is not None:
        xin = jnp.concatenate([cache, x], axis=1)          # (B,K,C)
        y = jnp.einsum("bkc,kc->bc", xin, w)[:, None]
        return y, xin[:, 1:]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # stack K shifted views — cheap for K=4, avoids conv lowering quirks
    y = sum(pad[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return y, None


def mamba_forward(p, x, *, cfg, ctx, cache=None):
    """Mamba-2 block. Returns (y (B,S,d), new_cache)."""
    b, s, d = x.shape
    g, n, h, pd = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    mode = ctx.mode if ctx else "train"
    hidden = rms_norm(x, p["ln"])
    z = jnp.einsum("bsd,de->bse", hidden, p["wz"])
    xs = jnp.einsum("bsd,de->bse", hidden, p["wx"])
    Bs = jnp.einsum("bsd,de->bse", hidden, p["wB"])
    Cs = jnp.einsum("bsd,de->bse", hidden, p["wC"])
    dt = jnp.einsum("bsd,dh->bsh", hidden, p["wdt"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if mode == "decode":
        cx, cB, cC = cache["conv_x"], cache["conv_B"], cache["conv_C"]
        xs, cx = _causal_conv(xs, p["conv_x"], cx)
        Bs, cB = _causal_conv(Bs, p["conv_B"], cB)
        Cs, cC = _causal_conv(Cs, p["conv_C"], cC)
        xs, Bs, Cs = map(jax.nn.silu, (xs, Bs, Cs))
        y1, state = ssd_decode_step(
            cache["state"], xs.reshape(b, h, pd), dt[:, 0],
            A, Bs.reshape(b, g, n), Cs.reshape(b, g, n))
        y = y1.reshape(b, 1, h, pd)
        xs_r = xs.reshape(b, 1, h, pd)
        new_cache = {"conv_x": cx, "conv_B": cB, "conv_C": cC, "state": state}
    else:
        xs, _ = _causal_conv(xs, p["conv_x"])
        Bs, _ = _causal_conv(Bs, p["conv_B"])
        Cs, _ = _causal_conv(Cs, p["conv_C"])
        xs, Bs, Cs = map(jax.nn.silu, (xs, Bs, Cs))
        xs_r = xs.reshape(b, s, h, pd)
        if cfg.attn_backend == "pallas":
            from repro.kernels import ops as kops
            y, state = kops.ssd_scan(xs_r, dt, A, Bs.reshape(b, s, g, n),
                                     Cs.reshape(b, s, g, n), cfg.ssm_chunk)
        else:
            y, state = ssd_chunked(xs_r, dt, A, Bs.reshape(b, s, g, n),
                                   Cs.reshape(b, s, g, n), cfg.ssm_chunk)
        if mode == "prefill":
            k = cfg.ssm_conv
            # conv tails need *pre-activation* streams; recompute cheaply
            new_cache = {
                "conv_x": _conv_tail(hidden, p["wx"], k),
                "conv_B": _conv_tail(hidden, p["wB"], k),
                "conv_C": _conv_tail(hidden, p["wC"], k),
                "state": state,
            }
        else:
            new_cache = None

    y = y + xs_r * p["D"][:, None].astype(y.dtype)
    y = y.reshape(b, -1, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"])
    return jnp.einsum("bse,ed->bsd", y, p["wout"]), new_cache


def _conv_tail(hidden, w_proj, k):
    tail = hidden[:, -(k - 1):]
    out = jnp.einsum("bsd,de->bse", tail, w_proj)
    pad = (k - 1) - tail.shape[1]
    if pad > 0:
        out = jnp.pad(out, ((0, 0), (pad, 0), (0, 0)))
    return out


# ---------------------------------------------------------------------------
# zamba2 shared block (applied once per repeat group, per-slot LoRA)
# ---------------------------------------------------------------------------

def init_shared_block(cfg, key):
    dt = jnp.dtype(cfg.dtype)
    d2 = 2 * cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((d2,), dt), "ln2": jnp.zeros((d2,), dt),
         "attn": init_attention(cfg, ks[0], d_in=d2),
         "mlp": init_mlp(cfg, ks[1], d_in=d2),
         "down": _init(ks[2], (d2, cfg.d_model), d2, dt)}
    return p


def init_shared_lora(cfg, key):
    """Per-slot LoRA for the shared block's qkv. Stacked over slots by the
    model assembly (one slot per repeat group)."""
    dt = jnp.dtype(cfg.dtype)
    d2 = 2 * cfg.d_model
    r = cfg.shared_lora_rank
    return {"a": _init(key, (3, d2, r), d2, dt),
            "b_q": jnp.zeros((r, cfg.n_heads, cfg.head_dim), dt),
            "b_k": jnp.zeros((r, cfg.n_kv_heads, cfg.head_dim), dt),
            "b_v": jnp.zeros((r, cfg.n_kv_heads, cfg.head_dim), dt)}


def shared_block_forward(p, lora, x, emb0, *, cfg, ctx, positions,
                         cache=None):
    """Zamba2: shared transformer block on concat(x, emb0) (2d wide),
    LoRA-adapted per slot, projected back to d and added to x."""
    h0 = jnp.concatenate([x, emb0], axis=-1)
    h = rms_norm(h0, p["ln1"])
    mode = ctx.mode if ctx else "train"
    q, k, v = _qkv(p["attn"], h, cfg, lora=lora)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    scale = q.shape[-1] ** -0.5
    if mode == "decode":
        kc, vc, valid = _cache_insert(cache, k, v, positions, None)
        out = _decode_attn(q, kc, vc, valid, scale, None)
        new_cache = {"k": kc, "v": vc}
    else:
        out = attention(q, k, v, causal=True, scale=scale,
                        backend=cfg.attn_backend)
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    out = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])
    h1 = h0 + out
    h2 = rms_norm(h1, p["ln2"])
    h1 = h1 + glu_mlp(h2, p["mlp"]["wi"], p["mlp"]["wo"], cfg.activation)
    return x + jnp.einsum("bse,ed->bsd", h1, p["down"]), new_cache
