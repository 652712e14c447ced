"""Shared neural-net primitives (pure JAX — no flax).

Everything here is written against the memory/compute profile of the
dry-run meshes: attention never materializes a full (S, T) score matrix
for long sequences (streamed log-sum-exp over KV blocks; windowed layers
slice only window+block keys per query block), reductions are fp32, and
shapes keep the head/ff dims as explicit axes so the sharding rules in
``repro.sharding`` can name them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# norms / activations / embeddings
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             zero_centered: bool = True) -> jax.Array:
    """RMSNorm; ``zero_centered`` follows gemma ((1+w)·x̂) which keeps init
    at identity with zero-init scales."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale) if zero_centered else scale
    return (x * w).astype(dtype)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def glu_mlp(x: jax.Array, wi: jax.Array, wo: jax.Array,
            activation: str) -> jax.Array:
    """wi: (d, 2, F) fused gate+up; wo: (F, d). activation in
    {geglu, swiglu, gelu, relu2}; non-GLU activations use wi[:, 0]."""
    if activation in ("geglu", "swiglu"):
        h = jnp.einsum("...d,dcf->...cf", x, wi)
        gate, up = h[..., 0, :], h[..., 1, :]
        act = jax.nn.gelu(gate, approximate=True) if activation == "geglu" \
            else jax.nn.silu(gate)
        h = act * up
    else:
        h = jnp.einsum("...d,df->...f", x, wi[:, 0])
        h = jax.nn.gelu(h) if activation == "gelu" else jnp.square(jax.nn.relu(h))
    return jnp.einsum("...f,fd->...d", h, wo)


def embed_tokens(tokens: jax.Array, table: jax.Array,
                 scale_by_dim: bool = False) -> jax.Array:
    out = jnp.take(table, tokens, axis=0)
    if scale_by_dim:     # gemma family scales embeddings by sqrt(d)
        out = out * jnp.asarray(math.sqrt(table.shape[1]), out.dtype)
    return out


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                          # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, dh/2)
    sin = jnp.sin(angles)[..., None, :]                    # (..., S, 1, dh/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — streamed (prefill), windowed (local layers), decode
# ---------------------------------------------------------------------------

NEG_INF = -2.0e38


def _gqa_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q: (B, S, Hq, D), k: (B, T, Hkv, D) -> scores (B, Hkv, G, S, T)
    where G = Hq // Hkv (grouped-query attention without repeating K)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, hq // hkv, d)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k) * scale


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B, Hkv, G, S, T), v: (B, T, Hkv, D) -> (B, S, Hq, D)."""
    b, hkv, g, s, _ = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, hkv * g, v.shape[-1])


def attention_streamed(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, scale: float,
                       attn_softcap: float | None = None,
                       prefix_len: jax.Array | None = None,
                       kv_block: int = 1024,
                       q_offset: jax.Array | int = 0,
                       vma_axes: tuple[str, ...] = (),
                       kv_vma_axes: tuple[str, ...] = ()) -> jax.Array:
    """Full attention with an online-softmax scan over KV blocks: peak
    memory is O(S·kv_block) instead of O(S·T). This is the pure-jnp
    oracle mirrored by the flash-attention Pallas kernel.

    ``prefix_len``: optional (B,) prefix-LM boundary — positions < prefix
    attend bidirectionally (PaliGemma-style)."""
    b, s, hq, d = q.shape
    dv = v.shape[-1]                 # may differ from d (MLA)
    t = k.shape[1]
    nblk = -(-t // kv_block)
    pad = nblk * kv_block - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if prefix_len is None:
        # flash custom-VJP path: O(S·kv_block) backward residuals
        q_pos = q_offset + jnp.arange(s)
        return _flash(q, k, v, q_pos, scale, causal, attn_softcap,
                      kv_block, tuple(vma_axes), t, tuple(kv_vma_axes))
    kb = k.reshape(b, nblk, kv_block, k.shape[2], d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, kv_block, v.shape[2], dv).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(s)    # global positions (seq-parallel)

    hkv = k.shape[2]
    g = hq // hkv
    acc0 = jnp.zeros((b, s, hq, dv), jnp.float32)
    m0 = jnp.full((b, hkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    if vma_axes:    # under shard_map the scan carry must be device-varying
        acc0, m0, l0 = (jax.lax.pvary(t, vma_axes) for t in (acc0, m0, l0))

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, idx = blk
        kv_pos = idx * kv_block + jnp.arange(kv_block)
        scores = _gqa_scores(q, kblk, scale).astype(jnp.float32)
        scores = softcap(scores, attn_softcap)
        mask = (kv_pos < t)[None, None, None, None, :]       # (1,1,1,1,Tb) pad
        if causal:
            cmask = (q_pos[:, None] >= kv_pos[None, :])[None]    # (1,S,Tb)
            if prefix_len is not None:
                pmask = kv_pos[None, :] < prefix_len[:, None]    # (B,Tb)
                cmask = cmask | pmask[:, None, :]                # (B,S,Tb)
            mask = mask & cmask[:, None, None]               # (B,1,1,S,Tb)
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p, vblk).reshape(b, s, hq, dv)
        corr_q = corr.transpose(0, 3, 1, 2).reshape(b, s, hq)
        acc_new = acc * corr_q[..., None] + pv
        return (acc_new, m_new, l_new), None

    # remat the block body: the scan's backward otherwise stacks every
    # block's probs (nblk × B×H×S×Tb fp32) — recomputing them per block
    # is the flash-backward trade (tiny extra FLOPs, O(S·Tb) memory)
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (acc0, m0, l0), (kb, vb, jnp.arange(nblk)))
    l_q = l.transpose(0, 3, 1, 2).reshape(b, s, hq)
    out = acc / jnp.maximum(l_q, 1e-37)[..., None]
    return out.astype(q.dtype)


def attention_windowed(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       window: int, scale: float,
                       attn_softcap: float | None = None,
                       q_block: int = 512,
                       q_offset: jax.Array | int = 0) -> jax.Array:
    """Sliding-window causal attention: scan over query blocks; each block
    sees a statically-sized (window + q_block) KV slice, so compute is
    O(S·window) — faithful FLOPs for the local layers of gemma-2/3.
    ``q_offset``: global position of q[0] (sequence-parallel shards pass
    their offset; k/v then cover the full sequence)."""
    b, s, hq, d = q.shape
    nblk = -(-s // q_block)
    pad = nblk * q_block - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    span = window + q_block                      # static KV slice length
    # kpad[j] = k at global position j - span; front pad covers the window
    # before position 0, back pad covers the last (possibly padded) q block
    kpad = jnp.pad(k, ((0, 0), (span, q_block + span), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (span, q_block + span), (0, 0), (0, 0)))
    qb = q.reshape(b, nblk, q_block, hq, d).transpose(1, 0, 2, 3, 4)

    def body(_, blk):
        qblk, i = blk
        start = q_offset + i * q_block
        # kpad[j] holds original position j - span; query block i needs
        # original positions [start - window, start + q_block), i.e. the
        # kpad slice starting at start + q_block of length span.
        kblk = jax.lax.dynamic_slice_in_dim(kpad, start + q_block, span, axis=1)
        vblk = jax.lax.dynamic_slice_in_dim(vpad, start + q_block, span, axis=1)
        q_pos = start + jnp.arange(q_block)
        kv_pos = start - window + jnp.arange(span)
        scores = _gqa_scores(qblk, kblk, scale).astype(jnp.float32)
        scores = softcap(scores, attn_softcap)
        delta = q_pos[:, None] - kv_pos[None, :]
        # HF sliding-window convention: q attends the last `window` keys
        # including itself (delta in [0, window)), matching the ring cache
        mask = (delta >= 0) & (delta < window) & (kv_pos[None, :] >= 0)
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return None, _gqa_out(probs.astype(qblk.dtype), vblk)

    _, outs = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), None,
                           (qb, jnp.arange(nblk)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nblk * q_block, hq, d)
    return out[:, :s]


def attention_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, *,
                     pos: jax.Array, scale: float,
                     attn_softcap: float | None = None,
                     window: int | None = None) -> jax.Array:
    """One-token decode against a (B, T, Hkv, D) cache. ``pos`` (scalar or
    (B,)): number of valid cache entries. GSPMD turns the reductions over
    a sequence-sharded cache into flash-decoding-style collectives."""
    b, one, hq, d = q.shape
    t = k_cache.shape[1]
    scores = _gqa_scores(q, k_cache, scale).astype(jnp.float32)   # (B,Hkv,G,1,T)
    scores = softcap(scores, attn_softcap)
    kv_pos = jnp.arange(t)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    mask = kv_pos[None, :] < pos_b[:, None]
    if window is not None:
        mask = mask & (kv_pos[None, :] > pos_b[:, None] - window)
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_out(probs, v_cache)


def attention(q, k, v, *, causal=True, window=None, scale=None,
              attn_softcap=None, prefix_len=None, backend="xla",
              q_offset=0, vma_axes=(), kv_vma_axes=()):
    """Prefill dispatcher. ``window`` selects the O(S·w) local path."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if backend == "pallas" and isinstance(q_offset, int) and q_offset == 0:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, scale=scale,
                                    window=window, attn_softcap=attn_softcap)
    if window is not None and causal:
        return attention_windowed(q, k, v, window=window, scale=scale,
                                  attn_softcap=attn_softcap, q_offset=q_offset)
    return attention_streamed(q, k, v, causal=causal, scale=scale,
                              attn_softcap=attn_softcap, prefix_len=prefix_len,
                              q_offset=q_offset, vma_axes=vma_axes,
                              kv_vma_axes=kv_vma_axes)


# ---------------------------------------------------------------------------
# flash custom-VJP: O(S·kv_block) residuals for the streamed attention
# ---------------------------------------------------------------------------
# Without this, the backward of the online-softmax scan stacks every
# block's carries (nblk × B·S·H fp32 buffers) — the dominant memory-term
# contributor on every train cell. The flash backward stores only
# (q, k, v, out, lse) and recomputes per-block probabilities.

from functools import partial as _partial


def _blocks(x, kv_block):
    b, t = x.shape[0], x.shape[1]
    nblk = t // kv_block
    return x.reshape(b, nblk, kv_block, *x.shape[2:]).transpose(
        1, 0, 2, *range(3, x.ndim + 1))


def _flash_mask(q_pos, kv_pos, t_valid, causal):
    mask = (kv_pos < t_valid)[None, :]
    if causal:
        mask = mask & (q_pos[:, None] >= kv_pos[None, :])
    return mask


@_partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_pos, scale, causal, softcap_v, kv_block, vma_axes,
           t_valid, kv_vma_axes):
    out, _ = _flash_fwd_impl(q, k, v, q_pos, scale, causal, softcap_v,
                             kv_block, vma_axes, t_valid)
    return out


def _flash_fwd_impl(q, k, v, q_pos, scale, causal, softcap_v, kv_block,
                    vma_axes, t_valid):
    b, s, hq, d = q.shape
    dv = v.shape[-1]
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    kb, vb = _blocks(k, kv_block), _blocks(v, kv_block)
    nblk = kb.shape[0]

    acc0 = jnp.zeros((b, s, hq, dv), jnp.float32)
    m0 = jnp.full((b, hkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    if vma_axes:
        acc0, m0, l0 = (jax.lax.pvary(x, vma_axes) for x in (acc0, m0, l0))

    def body(carry, blk):
        acc, m, l = carry
        kblk, vblk, idx = blk
        kv_pos = idx * kv_block + jnp.arange(kv_block)
        scores = _gqa_scores(q, kblk, scale).astype(jnp.float32)
        scores = softcap(scores, softcap_v)
        mask = _flash_mask(q_pos, kv_pos, t_valid, causal)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p, vblk).reshape(b, s, hq, dv)
        corr_q = corr.transpose(0, 3, 1, 2).reshape(b, s, hq)
        return (acc * corr_q[..., None] + pv, m_new, l_new), None

    (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                                  (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nblk)))
    l_q = l.transpose(0, 3, 1, 2).reshape(b, s, hq)
    out = (acc / jnp.maximum(l_q, 1e-37)[..., None]).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-37))            # (B,Hkv,G,S)
    return out, lse


def _flash_fwd(q, k, v, q_pos, scale, causal, softcap_v, kv_block, vma_axes,
               t_valid, kv_vma_axes):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, scale, causal, softcap_v,
                               kv_block, vma_axes, t_valid)
    return out, (q, k, v, q_pos, out, lse)


def _flash_bwd(scale, causal, softcap_v, kv_block, vma_axes, t_valid,
               kv_vma_axes, res, dout):
    q, k, v, q_pos, out, lse = res
    b, s, hq, d = q.shape
    dv = v.shape[-1]
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    kb, vb = _blocks(k, kv_block), _blocks(v, kv_block)
    nblk = kb.shape[0]
    dout = dout.astype(jnp.float32)
    # D = rowsum(dO * O) per query row, grouped layout (B,Hkv,G,S)
    delta = jnp.sum(dout * out.astype(jnp.float32), axis=-1)   # (B,S,Hq)
    delta = delta.reshape(b, s, hkv, g).transpose(0, 2, 3, 1)
    do_g = dout.reshape(b, s, hkv, g, dv)

    dq0 = jnp.zeros((b, s, hq, d), jnp.float32)
    if vma_axes:
        dq0 = jax.lax.pvary(dq0, vma_axes)

    def body(dq_acc, blk):
        kblk, vblk, idx = blk
        kv_pos = idx * kv_block + jnp.arange(kv_block)
        raw = _gqa_scores(q, kblk, scale).astype(jnp.float32)
        sc = softcap(raw, softcap_v)
        mask = _flash_mask(q_pos, kv_pos, t_valid, causal)
        sc = jnp.where(mask[None, None, None], sc, NEG_INF)
        p = jnp.exp(sc - lse[..., None])                       # (B,Hkv,G,S,T)
        dv_blk = jnp.einsum("bkgst,bskgd->btkd", p, do_g)
        dp = jnp.einsum("bskgd,btkd->bkgst", do_g, vblk)
        ds = p * (dp - delta[..., None])                       # d/d(sc)
        if softcap_v is not None:                              # through tanh
            ds = ds * (1.0 - jnp.square(sc / softcap_v))
        ds = jnp.where(mask[None, None, None], ds, 0.0)
        dq_blk = jnp.einsum("bkgst,btkd->bskgd", ds, kblk) * scale
        dk_blk = jnp.einsum("bkgst,bskgd->btkd", ds,
                            q.reshape(b, s, hkv, g, d)) * scale
        return dq_acc + dq_blk.reshape(b, s, hq, d), (dk_blk, dv_blk)

    dq, (dk_b, dv_b) = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), dq0,
        (kb, vb, jnp.arange(nblk)))
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(b, t, hkv, d)
    dv_ = dv_b.transpose(1, 0, 2, 3, 4).reshape(b, t, hkv, dv)
    # under shard_map, q (and thus ds) varies over axes K/V do not (the
    # sequence-parallel model axis): sum the shards' contributions
    psum_axes = tuple(a for a in vma_axes if a not in kv_vma_axes)
    if psum_axes:
        dk = jax.lax.psum(dk, psum_axes)
        dv_ = jax.lax.psum(dv_, psum_axes)
    import numpy as _np
    dpos = _np.zeros(jnp.shape(q_pos), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv_.astype(v.dtype),
            dpos)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  logit_softcap: float | None = None,
                  z_loss: float = 0.0) -> jax.Array:
    """Mean token cross-entropy in fp32 with optional z-loss."""
    logits = softcap(logits.astype(jnp.float32), logit_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = (lse - ll).mean()
    if z_loss:
        loss = loss + z_loss * jnp.square(lse).mean()
    return loss
