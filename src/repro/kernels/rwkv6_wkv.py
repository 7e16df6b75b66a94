"""Pallas TPU kernel: RWKV6 chunked WKV recurrence (data-dependent decay).

One grid cell per (batch*head); the chunk axis is the second grid dim
with the (P x P) state carried in VMEM scratch across chunk steps (same
carry idiom as the flash kernels).  Per chunk (L x P tiles in VMEM):

    cum_t   = prefix-sum of log w within the chunk        (L,P)
              (a lower-triangular matmul: Mosaic has no cumsum)
    A[t,j]  = (r_t e^{cum_{t-1}}) · (k_j e^{-cum_j}),  j<t    -> MXU matmul
    y       = A @ v + (u·(r k)) v   + (r e^{cum_{t-1}}) @ S
    S       = diag(e^{cum_L}) S + sum_j e^{cum_L - cum_j} k_j v_j^T

TPU adaptation notes: per-channel decay makes A non-factorizable through
a scalar like Mamba2's — the decay-weighted r'/k' trick keeps everything
as (L,P)x(P,L) MXU matmuls; the per-step log-decay clamp (|log w| <=
2.5) bounds e^{-cum} in f32 for chunk 32 (lossless: decay^32 underflows
anyway).  P=64 head dim and L=32 chunks keep tiles lane-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32
_HIGHEST = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sT_ref,
                s_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)      # (L,P)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)    # (L,P) <= 0
    u = u_ref[0].astype(jnp.float32)      # (1,P)

    l = r.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum); HIGHEST keeps the f32 log-decays exact on the MXU
    tril = jnp.where(tj <= ti, 1.0, 0.0)
    cum = jax.lax.dot_general(tril, lw, (((1,), (0,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)  # (L,P)
    cum_prev = cum - lw
    r_dec = r * jnp.exp(cum_prev)
    k_inc = k * jnp.exp(-cum)

    a = jax.lax.dot_general(r_dec, k_inc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L,L)
    a = jnp.where(tj < ti, a, 0.0)
    bonus = jnp.sum(r * u * k, axis=-1, keepdims=True)           # (L,1)

    s_prev = s_scr[...]                    # (P,P) key x value
    y = (jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + bonus * v
         + jax.lax.dot_general(r_dec, s_prev, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32))

    cum_last = cum[l - 1:l, :]             # (1,P) static slice
    wj = jnp.exp(cum_last - cum)           # (L,P)
    inc = jax.lax.dot_general(k * wj, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P,P)
    # diag(e^{cum_L}) S as a matmul: the decay indexes the state's rows,
    # which a (1,P) lane vector cannot broadcast over without a transpose
    pi = jax.lax.broadcasted_iota(jnp.int32, (s_prev.shape[0],) * 2, 0)
    pj = jax.lax.broadcasted_iota(jnp.int32, (s_prev.shape[0],) * 2, 1)
    decay = jnp.where(pi == pj, jnp.exp(cum_last), 0.0)          # (P,P)
    s_scr[...] = jax.lax.dot_general(
        decay, s_prev, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32) + inc

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit_state():
        sT_ref[0] = s_scr[...].astype(sT_ref.dtype)


def rwkv6_wkv(r, k, v, log_w, u, s0=None, *, chunk: int = DEFAULT_CHUNK,
              interpret: bool = False):
    """r/k/v (B,S,H,P); log_w (B,S,H,P) (<=0); u (H,P); s0 (B,H,P,P).

    Returns (y (B,S,H,P), s_final (B,H,P,P) f32).
    """
    b, s, h, p = r.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if s0 is None:
        s0 = jnp.zeros((b, h, p, p), jnp.float32)

    def to_bh(x):   # (B,S,H,P) -> (B*H, S, P)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, p)

    rr, kk, vv, ll = map(to_bh, (r, k, v, log_w))
    uu = jnp.broadcast_to(u[None, :, None, :], (b, h, 1, p)) \
        .reshape(b * h, 1, p)
    ss = s0.reshape(b * h, p, p)

    grid = (b * h, nc)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y, s_t = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, 1, p), lambda g, ci: (g, 0, 0)),
            pl.BlockSpec((1, p, p), lambda g, ci: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, p, p), lambda g, ci: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), r.dtype),
            jax.ShapeDtypeStruct((b * h, p, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, p), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ll, uu, ss)

    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y, s_t.reshape(b, h, p, p)
