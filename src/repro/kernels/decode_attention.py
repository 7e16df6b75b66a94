"""Pallas TPU flash-decode: one query token vs a long KV cache.

Decode attention is HBM-bandwidth-bound: per generated token the whole
cache (B x S x Hkv x D) streams through once.  The kernel tiles the cache
sequence dim into BLOCK_S VMEM tiles, one grid cell per (batch*kv_head,
s_block), carrying the online-softmax running (max, sum, acc) in VMEM
scratch across cache blocks.  The GQA query group (rep = H/Hkv heads)
rides in one (rep x D) VMEM tile and is reused against every cache tile —
the bandwidth argument for GQA.

``lengths`` masks the valid prefix of each sequence's cache (slot ==
position discipline of the serving runtime).

Consumers: the big-model serving decode step, and — via
``attn_impl="pallas"`` — the batched Marian decode path
(:meth:`repro.nmt.transformer.MarianTransformer.decode_step` with a
leading batch dim), which issues one call for self-attention against
the growing KV cache (lengths = pos+1) and one for cross-attention
against the precomputed encoder K/V (lengths = source lengths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 256
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_s: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (rep, D)
    k = k_ref[0]                                   # (block_s, D)
    v = v_ref[0]
    length = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (rep, block_s)
    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, lengths, *, scale=None,
                 block_s: int = DEFAULT_BLOCK_S, interpret: bool = False):
    """q (B,H,D); k/v_cache (B,S,Hkv,D); lengths (B,) -> (B,H,D)."""
    b, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else d ** -0.5
    assert s % block_s == 0, (s, block_s)

    qr = q.reshape(b, hkv, rep, d).reshape(b * hkv, rep, d)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), hkv)     # (B*Hkv,)

    grid = (b * hkv, s // block_s)
    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s)
    # lengths ride in SMEM by scalar prefetch; index maps take them last
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, rep, d), lambda g, si, lens: (g, 0, 0)),
                pl.BlockSpec((1, block_s, d), lambda g, si, lens: (g, si, 0)),
                pl.BlockSpec((1, block_s, d), lambda g, si, lens: (g, si, 0)),
            ],
            out_specs=pl.BlockSpec((1, rep, d), lambda g, si, lens: (g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rep, 1), jnp.float32),
                pltpu.VMEM((rep, 1), jnp.float32),
                pltpu.VMEM((rep, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * hkv, rep, d), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(b, hkv, rep, d).reshape(b, h, d)
