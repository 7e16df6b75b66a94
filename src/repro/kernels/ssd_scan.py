"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Grid (batch*head, chunk); carry = the (P x N) SSM state in VMEM scratch.
Per chunk with L-row tiles (x (L,P), b/c (L,N), dt/log-decay (L,1), and
dt/log-decay again as (1,L) rows, so no transpose is needed in-kernel):

    cum     = prefix-sum log decay                      (L,1) per-head scalar
    CB      = c @ b^T, masked lower-triangular, * e^{cum_t-cum_j} * dt_j
    y       = CB @ x  +  (c * e^{cum}) @ S
    S       = e^{cum_L} S + (b * dt * e^{cum_L - cum})^T @ x

Mamba2's scalar-per-head decay factorizes through the (L,L) score matrix
directly (unlike RWKV6's per-channel decay) so the mask/decay is an
elementwise multiply on the MXU matmul output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, ld_ref, dtr_ref, ldr_ref, s0_ref,
                y_ref, sT_ref, s_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)       # (L,P)
    bb = b_ref[0].astype(jnp.float32)      # (L,N)
    cc = c_ref[0].astype(jnp.float32)      # (L,N)
    dt = dt_ref[0].astype(jnp.float32)     # (L,1)
    ld = ld_ref[0].astype(jnp.float32)     # (L,1) <= 0
    dt_row = dtr_ref[0, 0].astype(jnp.float32)   # (1,L) same values
    ld_row = ldr_ref[0, 0].astype(jnp.float32)   # (1,L)

    l = x.shape[0]
    n = bb.shape[1]
    ti = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    tril = jnp.where(tj <= ti, 1.0, 0.0)
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum), in both layouts: cum_t down the sublanes, cum_j along lanes
    cum = jax.lax.dot_general(tril, ld, (((1,), (0,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)   # (L,1)
    cum_row = jax.lax.dot_general(ld_row, tril, (((1,), (1,)), ((), ())),
                                  precision=_HIGHEST,
                                  preferred_element_type=jnp.float32)  # (1,L)
    cb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L,L)
    seg = jnp.where(tj <= ti, cum - cum_row, -jnp.inf)  # cum_t - cum_j, j<=t
    scores = cb * jnp.exp(seg) * dt_row

    s_prev = s_scr[...]                    # (N,P) state (key-major)
    y = (jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(cc * jnp.exp(cum), s_prev,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32))

    cum_last = cum[l - 1:l, :]             # (1,1) static slice
    wj = jnp.exp(cum_last - cum) * dt      # (L,1)
    inc = jax.lax.dot_general(bb * wj, x, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (N,P)
    # the chunk's total decay as an (N,1) column, reduced from the lane
    # layout: Mosaic cannot broadcast one scalar over both state axes
    decay = jnp.exp(jnp.sum(jnp.broadcast_to(ld_row, (n, l)), axis=1,
                            keepdims=True))
    s_scr[...] = s_prev * decay + inc

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit():
        sT_ref[0] = s_scr[...].astype(sT_ref.dtype)


def ssd_scan(x, dt, a_log, b_in, c_in, s0=None, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool = False):
    """x (B,S,H,P); dt (B,S,H) post-softplus; a_log (H,); b/c (B,S,H,N).

    Returns (y (B,S,H,P), s_final (B,H,P,N) f32) matching
    ``repro.kernels.ref.ssd_ref``.
    """
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))                 # (H,)
    log_decay = dt.astype(jnp.float32) * a[None, None, :]   # (B,S,H)

    def to_bh(t, d_last):
        return t.transpose(0, 2, 1, 3).reshape(bsz * h, s, d_last)

    xx = to_bh(x, p)
    bb = to_bh(b_in, n)
    cc = to_bh(c_in, n)
    dd = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(bsz * h, s, 1)
    ll = log_decay.transpose(0, 2, 1).reshape(bsz * h, s, 1)
    if s0 is None:
        s0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    # kernel state is key-major (N,P)
    ss = s0.transpose(0, 1, 3, 2).reshape(bsz * h, n, p)

    grid = (bsz * h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, s_t = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda g, ci: (g, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda g, ci: (g, ci, 0, 0)),
            pl.BlockSpec((1, n, p), lambda g, ci: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, n, p), lambda g, ci: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz * h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xx, bb, cc, dd, ll, dd.reshape(bsz * h, nc, 1, chunk),
      ll.reshape(bsz * h, nc, 1, chunk), ss)

    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
    s_t = s_t.reshape(bsz, h, n, p).transpose(0, 1, 3, 2)   # back to (P,N)
    return y, s_t
