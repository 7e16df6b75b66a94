"""Mamba2 (SSD — state-space duality) mixer.

Recurrence per head h with state H (P, N):
    H_t = a_t * H_{t-1} + dt_t * x_t ⊗ B_t        a_t = exp(dt_t * A_h) ∈ (0,1)
    y_t = H_t @ C_t + D_h * x_t

Training/prefill uses the *chunked* SSD algorithm (TPU-idiomatic: chunk
matmuls hit the MXU; the sequential dependency is reduced to one scan over
S/chunk inter-chunk states instead of S steps).  Decode is the O(1) state
update — the property that makes 500k-token contexts feasible
(DESIGN.md §Arch-applicability).

Shapes: x (B,S,D); inner width d_in = expand*D split into nh = d_in/P
heads; B/C are shared across heads within n_groups groups.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, SSMConfig
from repro.models.layers.basic import linear, linear_params, rmsnorm


def pick_chunk(seq: int, chunk: int) -> int:
    """Largest divisor of ``seq`` that is <= ``chunk`` (production shapes
    divide exactly; odd smoke/prefill lengths degrade gracefully)."""
    l = min(chunk, seq)
    while seq % l:
        l -= 1
    return max(l, 1)


class MambaState(NamedTuple):
    ssm: jnp.ndarray     # (B, nh, P, N)
    conv: jnp.ndarray    # (B, conv_width-1, conv_channels) rolling buffer


def mamba2_params(key, cfg: ModelConfig, dtype=jnp.float32):
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.state_dim
    ks = jax.random.split(key, 4)
    # in_proj emits [z, x, B, C, dt]
    return {
        "in_proj": linear_params(ks[0], d, 2 * d_in + 2 * s.n_groups * s.state_dim + nh, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_ch), jnp.float32)
                   * (s.conv_width ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_g": {"g": jnp.ones((d_in,), jnp.float32)},
        "out_proj": linear_params(ks[2], d_in, d, dtype),
    }


def _split_proj(cfg: ModelConfig, zxbcdt):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    gn = s.n_groups * s.state_dim
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * gn], axis=-1)
    return z, xbc, dt


def _causal_conv_full(p, xbc):
    """Depthwise causal conv over (B,S,C) with window W; silu activation."""
    w = p["conv_w"].astype(xbc.dtype)                  # (W, C)
    wwidth = w.shape[0]
    pads = jnp.pad(xbc, ((0, 0), (wwidth - 1, 0), (0, 0)))
    # sum_k x[t-W+1+k] * w[k]
    out = sum(pads[:, k:k + xbc.shape[1], :] * w[k] for k in range(wwidth))
    return jax.nn.silu(out + p["conv_b"].astype(xbc.dtype))


def _heads(cfg, x_in, b_in, c_in):
    s = cfg.ssm
    b_, seq = x_in.shape[0], x_in.shape[1]
    nh = (s.expand * cfg.d_model) // s.head_dim
    x = x_in.reshape(b_, seq, nh, s.head_dim)
    bb = b_in.reshape(b_, seq, s.n_groups, s.state_dim)
    cc = c_in.reshape(b_, seq, s.n_groups, s.state_dim)
    # broadcast groups over heads
    rep = nh // s.n_groups
    bb = jnp.repeat(bb, rep, axis=2)
    cc = jnp.repeat(cc, rep, axis=2)
    return x, bb, cc


def mamba2_full(p, cfg: ModelConfig, x, *,
                impl: str = "xla") -> Tuple[jnp.ndarray, MambaState]:
    """Chunked SSD over a full sequence. Returns (y (B,S,D), final state).

    ``impl="pallas"`` dispatches the inner SSD scan to the
    :func:`repro.kernels.ops.ssd_scan` Pallas kernel (interpret mode on
    CPU, Mosaic on TPU); ``"xla"`` keeps the pure-jnp chunked scan.  Both
    compute the same chunk algorithm (the kernel with 8-aligned chunks of
    ``cfg.ssm.chunk``) and agree to float32 rounding, pinned in
    tests/test_bigmodel_serving.py.
    """
    s = cfg.ssm
    b, seq, _ = x.shape
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim

    zxbcdt = linear(p["in_proj"], x)
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    xbc = _causal_conv_full(p, xbc)
    x_in, b_in, c_in = jnp.split(
        xbc, [d_in, d_in + s.n_groups * s.state_dim], axis=-1)
    xh, bh, ch = _heads(cfg, x_in, b_in, c_in)          # (B,S,nh,P),(B,S,nh,N)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"])                # (B,S,nh)
    a = -jnp.exp(p["a_log"])                            # (nh,) negative
    log_decay = dt * a                                  # (B,S,nh)  <= 0

    if impl == "pallas":
        from repro.kernels.ops import ssd_scan
        y, h_final = ssd_scan(
            xh.astype(jnp.float32), dt, p["a_log"],
            bh.astype(jnp.float32), ch.astype(jnp.float32), chunk=s.chunk)
        y = y.astype(xh.dtype)
        h_final = h_final.astype(xh.dtype)
        y = y + xh * p["d_skip"][None, None, :, None].astype(xh.dtype)
        y = y.reshape(b, seq, d_in)
        y = rmsnorm(p["norm_g"], y * jax.nn.silu(z), cfg.norm_eps)
        y = linear(p["out_proj"], y)
        zxbcdt_tail = _split_proj(
            cfg, linear(p["in_proj"], x[:, -(s.conv_width - 1):, :]))[1]
        return y, MambaState(ssm=h_final, conv=zxbcdt_tail)

    from repro.sharding.ctx import constrain_batch

    L = pick_chunk(seq, s.chunk)
    nc = seq // L

    def chunked(xh, bh, ch, dt, log_decay):
        # chunk-major (NC,B,L,...) for a scan over chunks: per-chunk
        # intermediates only (the vectorized-over-NC form made backward
        # hold full-sequence (B,NC,nh,L,L) tensors; see §Perf iter 2).
        def toc(t):
            return jnp.moveaxis(t.reshape(b, nc, L, *t.shape[2:]), 1, 0)

        xs = (toc(xh), toc(bh), toc(ch), toc(dt), toc(log_decay))
        tri = jnp.tril(jnp.ones((L, L), bool))

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def chunk_body(h_prev, inp):
            xc, bc, cc, dtc, ld = inp                   # (B,L,nh,·)
            cum = jnp.cumsum(ld, axis=1)                # (B,L,nh)
            # intra: scores[t,j] = C_t·B_j exp(cum_t-cum_j) dt_j, j<=t
            cb = jnp.einsum("blhs,bmhs->bhlm", cc, bc)  # (B,nh,L,L)
            seg = cum[:, :, None, :] - cum[:, None, :, :]   # (B,L,L,nh)
            seg = jnp.moveaxis(seg, -1, 1)              # (B,nh,L,L)
            # mask BEFORE exp: for j>t seg>0 overflows -> 0*inf NaN grads
            seg = jnp.where(tri, seg, -jnp.inf)
            scores = constrain_batch(cb * jnp.exp(seg).astype(cb.dtype))
            scores = scores * jnp.moveaxis(dtc, -1, 1)[:, :, None, :] \
                .astype(cb.dtype)
            y = jnp.einsum("bhlm,bmhp->blhp", scores, xc)
            # inter: y += C_t · (exp(cum_t) * H_start)
            wi = jnp.exp(cum)                           # (B,L,nh)
            y = y + jnp.einsum("blhs,bhps,blh->blhp", cc, h_prev,
                               wi.astype(cc.dtype))
            # state: H_end = exp(cum_L) H_start + sum_j exp(cum_L-cum_j) dt_j B_j x_j
            wj = jnp.exp(cum[:, -1:, :] - cum) * dtc    # (B,L,nh)
            hc = jnp.einsum("blh,blhs,blhp->bhps", wj.astype(xc.dtype),
                            bc, xc)
            h_new = h_prev * jnp.exp(cum[:, -1, :])[:, :, None, None] \
                .astype(h_prev.dtype) + hc
            return h_new, y

        h0 = jnp.zeros((b, nh, s.head_dim, s.state_dim), xh.dtype)
        h_final, ys = jax.lax.scan(chunk_body, h0, xs)
        return jnp.moveaxis(ys, 0, 1).reshape(b, seq, nh, s.head_dim), h_final

    y, h_final = chunked(xh, bh, ch, dt, log_decay)
    y = y + xh * p["d_skip"][None, None, :, None].astype(xh.dtype)
    y = y.reshape(b, seq, d_in)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = rmsnorm(p["norm_g"], y * jax.nn.silu(z), cfg.norm_eps)
    y = linear(p["out_proj"], y)

    # rolling conv buffer = last (W-1) pre-activation conv inputs
    zxbcdt_tail = _split_proj(cfg, linear(p["in_proj"], x[:, -(s.conv_width - 1):, :]))[1]
    state = MambaState(ssm=h_final, conv=zxbcdt_tail)
    return y, state


def mamba2_decode(p, cfg: ModelConfig, x, state: MambaState
                  ) -> Tuple[jnp.ndarray, MambaState]:
    """Single-token state update. x (B,1,D)."""
    s = cfg.ssm
    b = x.shape[0]
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim

    zxbcdt = linear(p["in_proj"], x)
    z, xbc_new, dt_raw = _split_proj(cfg, zxbcdt)       # (B,1,·)

    # causal conv against rolling buffer
    window = jnp.concatenate([state.conv, xbc_new], axis=1)   # (B,W,C)
    w = p["conv_w"].astype(x.dtype)
    conv_out = jnp.einsum("bwc,wc->bc", window, w) + p["conv_b"].astype(x.dtype)
    xbc = jax.nn.silu(conv_out)[:, None, :]

    x_in, b_in, c_in = jnp.split(
        xbc, [d_in, d_in + s.n_groups * s.state_dim], axis=-1)
    xh, bh, ch = _heads(cfg, x_in, b_in, c_in)
    xh, bh, ch = xh[:, 0], bh[:, 0], ch[:, 0]           # (B,nh,P),(B,nh,N)

    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt * a)                             # (B,nh)

    h = state.ssm * decay[:, :, None, None].astype(state.ssm.dtype)
    h = h + jnp.einsum("bh,bhp,bhs->bhps",
                       dt.astype(xh.dtype), xh, bh)
    y = jnp.einsum("bhps,bhs->bhp", h, ch)
    y = y + xh * p["d_skip"][None, :, None].astype(xh.dtype)
    y = y.reshape(b, 1, d_in)
    y = rmsnorm(p["norm_g"], y * jax.nn.silu(z), cfg.norm_eps)
    y = linear(p["out_proj"], y)

    new_conv = window[:, 1:, :]
    return y, MambaState(ssm=h, conv=new_conv)


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> MambaState:
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.state_dim
    return MambaState(
        ssm=jnp.zeros((batch, nh, s.head_dim, s.state_dim), dtype),
        conv=jnp.zeros((batch, s.conv_width - 1, conv_ch), dtype),
    )
