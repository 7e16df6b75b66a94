"""Pallas TPU flash attention (prefill/training forward).

TPU adaptation of the flash schedule (DESIGN.md §6): the grid walks
(batch*kv_head, q_block, k_block) with K innermost so the output block
accumulates in VMEM across K steps; online softmax keeps running max/sum
per row.  BlockSpecs stage (BLOCK_Q x head_dim) query tiles and
(BLOCK_K x head_dim) key/value tiles HBM->VMEM; head_dim and the block
sizes are multiples of the 128-lane MXU tiling.

GQA: the q tile carries the `rep` query heads of one kv head
(rep*head_dim lanes), so every staged K/V tile is reused by all grouped
queries — the same reuse argument that makes GQA decode bandwidth-
efficient on TPU.

Causal masking is positional (no mask tensor).  Fully-masked K blocks
are still visited and masked, not pruned from the grid: about 2x the
needed work on long causal prompts, open for tuning on the chip.

Padded batches: ``lengths`` (B,) optionally masks each sequence's valid
KEY prefix (slot < length), the prefix-padding discipline of the serving
batcher — this is how the batched Marian encoder/teacher-forced path
routes ragged length-bucketed batches through the kernel without
pre-trimming.  Rows whose query position is padding attend only to valid
keys (garbage-in-padding stays confined to padding rows).  ``lengths``
must be >= 1: a fully-masked row degenerates to exp(0)=1 weights on
every key (the online-softmax max never leaves NEG_INF), same contract
as the decode kernel and ``ref.attention_ref``; callers clamp.  The
lengths reach the kernel in SMEM by scalar prefetch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, causal: bool, block_q: int,
                  block_k: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (block_q, rep*d)
    k = k_ref[0]                       # (block_k, d)
    v = v_ref[0]
    d = k.shape[-1]
    rep = q.shape[-1] // d
    bq = q.shape[0]

    qh = q.reshape(bq * rep, d) if rep > 1 else q
    s = jax.lax.dot_general(
        qh.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (bq*rep, block_k)

    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq * rep, block_k), 1)
    s = jnp.where(k_pos < len_ref[pl.program_id(0)], s, NEG_INF)  # key prefix
    if causal:
        q_pos = (qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, rep, block_k), 0)).reshape(bq * rep, block_k)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]                # (bq*rep, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)             # (bq*rep, block_k)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).reshape(bq, rep * d).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None,
                    lengths=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q (B,S,H,D); k/v (B,T,Hkv,D) -> (B,S,H,D).

    S % block_q == 0 and T % block_k == 0 required (production shapes are
    powers of two; ops.py pads otherwise).  ``lengths`` (B,) int32
    optionally restricts each sequence to its valid key prefix (padded
    batch discipline); None means all T keys are valid.
    """
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else d ** -0.5
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)

    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)

    # (B*Hkv, S, rep*D): group query heads with their kv head
    qr = (q.reshape(b, s, hkv, rep, d).transpose(0, 2, 1, 3, 4)
          .reshape(b * hkv, s, rep * d))
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), hkv)      # (B*Hkv,)

    grid = (b * hkv, s // block_q, t // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=t)

    # lengths ride in SMEM by scalar prefetch; index maps take them last
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, rep * d),
                             lambda g, qi, ki, lens: (g, qi, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda g, qi, ki, lens: (g, ki, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda g, qi, ki, lens: (g, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, rep * d),
                                   lambda g, qi, ki, lens: (g, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q * rep, 1), jnp.float32),   # running max
                pltpu.VMEM((block_q * rep, 1), jnp.float32),   # running sum
                pltpu.VMEM((block_q * rep, d), jnp.float32),   # o accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b * hkv, s, rep * d), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr)

    return (out.reshape(b, hkv, s, rep, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, s, h, d))
