"""Flash attention (fwd) Pallas TPU kernel.

TPU adaptation of the FlashAttention blocking (arXiv:2205.14135) — this
framework's prefill hot-spot. Grid = (batch·kv_heads, q_blocks); the
kernel streams KV blocks through VMEM with the online-softmax recurrence
entirely in fp32 VREGs. Block shapes are MXU-aligned (multiples of 128 on
the contracting/lane dims, head_dim padded by the BlockSpec machinery).

Causal block skipping: KV blocks strictly above the diagonal contribute
nothing; the kernel computes them masked (uniform grid) but the *windowed*
variant bounds the KV range structurally — on TPU the win comes from
keeping the systolic array busy on the valid region, which the index map
provides by construction for local attention.

Oracle: ``repro.kernels.ref.flash_attention_ref`` (== the model's
streamed-attention path). Validated in interpret mode on CPU; compiled
path targets real TPUs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                 q_block, kv_block, seq_len, softcap):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (q_block, dh)

    m = jnp.full((q_block,), NEG_INF, jnp.float32)
    l = jnp.zeros((q_block,), jnp.float32)
    acc = jnp.zeros((q_block, v_ref.shape[-1]), jnp.float32)

    n_kv = seq_len // kv_block
    q_pos = qi * q_block + jax.lax.iota(jnp.int32, q_block)

    def body(kv_i, carry):
        m, l, acc = carry
        kv = pl.ds(kv_i * kv_block, kv_block)
        k = k_ref[0, kv, :].astype(jnp.float32)
        v = v_ref[0, kv, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kv_pos = kv_i * kv_block + jax.lax.iota(jnp.int32, kv_block)
        mask = jnp.ones((q_block, kv_block), jnp.bool_)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window is not None:   # HF convention: last `window` keys incl. self
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # causal: only blocks up to (and including) the diagonal
    hi = n_kv if not causal else \
        jnp.minimum(n_kv, (qi + 1) * q_block // kv_block + 1)
    lo = 0 if window is None else \
        jnp.maximum(0, (qi * q_block - window) // kv_block)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "window",
                                             "softcap", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal=True, scale=None, window=None,
                    softcap=None, q_block=512, kv_block=512,
                    interpret=False):
    """q (B, S, Hq, D); k/v (B, S, Hkv, D[v]). GQA folded into the grid:
    each q-head group attends its kv head."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    assert s % q_block == 0 and s % kv_block == 0

    # layout: (B*Hq, S, D) for q; (B*Hkv, S, D) for kv
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, dv)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, seq_len=s, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, s // q_block),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, s, d), lambda h, i, g=g: (h // g, 0, 0)),
            pl.BlockSpec((1, s, dv), lambda h, i, g=g: (h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, dv), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, dv), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, s, dv).transpose(0, 2, 1, 3)
