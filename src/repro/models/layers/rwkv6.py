"""RWKV6 ("Finch") mixers: time-mix with data-dependent decay + channel-mix.

Per head (P = head_dim) the time-mix recurrence over state S (P_k x P_v):

    y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

with the *data-dependent* per-channel decay w_t = exp(-exp(w0 + lora(x)))
— Finch's contribution over RWKV5's static decay [arXiv:2404.05892].

Training/prefill uses a chunked formulation (TPU adaptation: chunk-local
matmuls instead of a 1-token/step scan).  Because the decay is per-channel
(not per-head-scalar like Mamba2), the intra-chunk term factorizes through
decay-weighted r' = r*exp(cum) and k' = k*exp(-cum); stability is
guaranteed by clamping the per-step log-decay (|log w| <= CLAMP), which is
lossless in practice since decay^chunk underflows anyway.

Decode is the O(1) recurrence — RWKV has *no KV cache*, which is why
rwkv6-3b runs the 500k-context shape.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, RWKVConfig
from repro.models.layers.basic import linear, linear_params

LOG_DECAY_CLAMP = 2.5   # per-step |log w| bound; exp(2.5*chunk) stays in f32


class RWKVState(NamedTuple):
    wkv: jnp.ndarray      # (B, H, P, P) time-mix state
    shift_tm: jnp.ndarray  # (B, D) previous token (time-mix shift)
    shift_cm: jnp.ndarray  # (B, D) previous token (channel-mix shift)


def rwkv6_params(key, cfg: ModelConfig, dtype=jnp.float32):
    r: RWKVConfig = cfg.rwkv
    d = cfg.d_model
    ks = jax.random.split(key, 10)
    h = d // r.head_dim
    return {
        # token-shift interpolation coefficients per stream
        "mix": {name: (0.5 * jnp.ones((d,), jnp.float32))
                for name in ("r", "k", "v", "g", "w")},
        "r": linear_params(ks[0], d, d, dtype),
        "k": linear_params(ks[1], d, d, dtype),
        "v": linear_params(ks[2], d, d, dtype),
        "g": linear_params(ks[3], d, d, dtype),
        # data-dependent decay LoRA: d -> rank -> d
        "w_down": linear_params(ks[4], d, r.decay_lora, dtype),
        "w_up": linear_params(ks[5], r.decay_lora, d, dtype),
        "w0": (-1.0 * jnp.ones((d,), jnp.float32)),
        "u": (jnp.zeros((h, r.head_dim), jnp.float32)),   # bonus
        "ln_g": jnp.ones((d,), jnp.float32),              # group norm scale
        "ln_b": jnp.zeros((d,), jnp.float32),
        "o": linear_params(ks[6], d, d, dtype),
    }


def channel_mix_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    dh = int(3.5 * d)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "mix": {name: 0.5 * jnp.ones((d,), jnp.float32) for name in ("r", "k")},
        "rk": linear_params(k1, d, d, dtype),
        "kk": linear_params(k2, d, dh, dtype),
        "vv": linear_params(k3, dh, d, dtype),
    }


def _token_shift(x, prev):
    """shifted[t] = x[t-1]; shifted[0] = prev (carry across calls)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _mix(mix_coef, x, x_prev):
    c = mix_coef.astype(x.dtype)
    return x + (x_prev - x) * c


def _streams(p, cfg, x, shift_prev):
    """Project the five time-mix streams. x (B,S,D)."""
    r_cfg = cfg.rwkv
    xs = _token_shift(x, shift_prev)
    r = linear(p["r"], _mix(p["mix"]["r"], x, xs))
    k = linear(p["k"], _mix(p["mix"]["k"], x, xs))
    v = linear(p["v"], _mix(p["mix"]["v"], x, xs))
    g = linear(p["g"], _mix(p["mix"]["g"], x, xs))
    wx = _mix(p["mix"]["w"], x, xs)
    w_log = p["w0"] + linear(p["w_up"], jnp.tanh(linear(p["w_down"], wx))
                             ).astype(jnp.float32)
    # per-step log decay, clamped for chunked stability
    log_w = -jnp.clip(jnp.exp(w_log), 1e-4, LOG_DECAY_CLAMP)   # (B,S,D) <= 0
    return r, k, v, g, log_w


def _group_norm(p, y, eps, heads):
    """Per-head LayerNorm over P (RWKV's ln_x), then flatten."""
    b, s, h, pp = y.shape
    yf = y.astype(jnp.float32)
    mu = yf.mean(-1, keepdims=True)
    var = ((yf - mu) ** 2).mean(-1, keepdims=True)
    yn = (yf - mu) * jax.lax.rsqrt(var + eps)
    yn = yn.reshape(b, s, h * pp) * p["ln_g"] + p["ln_b"]
    return yn


def rwkv6_full(p, cfg: ModelConfig, x, state: RWKVState, *,
               impl: str = "xla") -> Tuple[jnp.ndarray, RWKVState]:
    """Chunked WKV over a full sequence. Returns (y (B,S,D), final state).

    ``impl="pallas"`` dispatches the inner WKV recurrence to the
    :func:`repro.kernels.ops.rwkv6_wkv` Pallas kernel (interpret mode on
    CPU, Mosaic on TPU); ``"xla"`` keeps the pure-jnp chunked scan.  Both
    compute the same chunk algorithm and agree to float32 rounding,
    pinned in tests/test_bigmodel_serving.py.
    """
    rc = cfg.rwkv
    b, seq, d = x.shape
    hnum, pdim = d // rc.head_dim, rc.head_dim

    r, k, v, g, log_w = _streams(p, cfg, x, state.shift_tm)
    rh = r.reshape(b, seq, hnum, pdim)
    kh = k.reshape(b, seq, hnum, pdim)
    vh = v.reshape(b, seq, hnum, pdim)
    lw = log_w.reshape(b, seq, hnum, pdim)               # f32

    if impl == "pallas":
        from repro.kernels.ops import rwkv6_wkv
        y, s_final = rwkv6_wkv(
            rh.astype(jnp.float32), kh.astype(jnp.float32),
            vh.astype(jnp.float32), lw, p["u"],
            state.wkv.astype(jnp.float32))
        y = _group_norm(p, y, cfg.norm_eps, hnum)
        y = (y * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
        y = linear(p["o"], y)
        new_state = RWKVState(wkv=s_final.astype(state.wkv.dtype),
                              shift_tm=x[:, -1, :],
                              shift_cm=state.shift_cm)
        return y, new_state

    from repro.models.layers.mamba2 import pick_chunk
    from repro.sharding.ctx import constrain_batch

    L = pick_chunk(seq, 32)
    nc = seq // L

    # (NC,B,L,H,P) chunk-major for the scan
    def to_chunks(x):
        return jnp.moveaxis(x.reshape(b, nc, L, hnum, pdim), 1, 0)

    xs = (to_chunks(rh), to_chunks(kh), to_chunks(vh), to_chunks(lw))
    tri = jnp.tril(jnp.ones((L, L), bool), k=-1)         # strictly lower: j<t

    # One chunk at a time: per-chunk intermediates are (B,L,H,P)/(B,H,L,L)
    # and the remat'd body keeps backward peak memory per-chunk too (the
    # vectorized-over-NC form holds ~16 full-sequence f32 tensors during
    # backward — tens of GB/device at train_4k; see EXPERIMENTS.md §Perf).
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk_body(s_prev, inp):
        rC, kC, vC, lwC = (t.astype(jnp.float32) for t in inp)  # (B,L,H,P)
        cum = jnp.cumsum(lwC, axis=1)                    # (B,L,H,P) <= 0
        cum_prev = cum - lwC
        # intra: A[t,j] = sum_c r_t,c k_j,c exp(cum_prev_t - cum_j), j<t
        r_dec = constrain_batch(rC * jnp.exp(cum_prev))
        k_inc = constrain_batch(kC * jnp.exp(-cum))
        a = jnp.einsum("blhp,bmhp->bhlm", r_dec, k_inc)  # (B,H,L,L)
        a = jnp.where(tri, a, 0.0)
        bonus = jnp.einsum("blhp,hp,blhp->blh", rC, p["u"], kC)
        y = jnp.einsum("bhlm,bmhp->blhp", a, vC)
        y = y + bonus[..., None] * vC
        # inter: y_t += (r_t * exp(cum_prev_t)) · S_start
        y = y + jnp.einsum("blhp,bhpq->blhq", r_dec, s_prev)
        # state: S_end = diag(exp(cum_L)) S_start + sum_j exp(cum_L-cum_j) kv
        wj = jnp.exp(cum[:, -1:, :, :] - cum)            # (B,L,H,P)
        inc = jnp.einsum("blhp,blhq->bhpq", kC * wj, vC)
        s_new = s_prev * jnp.exp(cum[:, -1, :, :])[..., None] + inc
        return s_new, y

    s_final, ys = jax.lax.scan(chunk_body, state.wkv.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, seq, hnum, pdim)

    y = _group_norm(p, y, cfg.norm_eps, hnum)
    y = (y * jax.nn.silu(g.astype(jnp.float32))).astype(x.dtype)
    y = linear(p["o"], y)
    new_state = RWKVState(wkv=s_final.astype(state.wkv.dtype),
                          shift_tm=x[:, -1, :],
                          shift_cm=state.shift_cm)
    return y, new_state


def rwkv6_decode(p, cfg: ModelConfig, x, state: RWKVState
                 ) -> Tuple[jnp.ndarray, RWKVState]:
    """One-token recurrence. x (B,1,D)."""
    rc = cfg.rwkv
    b, _, d = x.shape
    hnum, pdim = d // rc.head_dim, rc.head_dim
    r, k, v, g, log_w = _streams(p, cfg, x, state.shift_tm)
    rh = r.reshape(b, hnum, pdim).astype(jnp.float32)
    kh = k.reshape(b, hnum, pdim).astype(jnp.float32)
    vh = v.reshape(b, hnum, pdim).astype(jnp.float32)
    w = jnp.exp(log_w.reshape(b, hnum, pdim))            # (B,H,P)

    s_prev = state.wkv.astype(jnp.float32)               # (B,H,P,P)
    kv = jnp.einsum("bhp,bhq->bhpq", kh, vh)
    y = jnp.einsum("bhp,bhpq->bhq", rh, s_prev + p["u"][None, :, :, None] * kv)
    s_new = s_prev * w[..., None] + kv

    y = _group_norm(p, y.reshape(b, 1, hnum, pdim), cfg.norm_eps, hnum)
    y = (y * jax.nn.silu(g.reshape(b, 1, d).astype(jnp.float32))).astype(x.dtype)
    y = linear(p["o"], y)
    return y, RWKVState(wkv=s_new.astype(state.wkv.dtype),
                        shift_tm=x[:, -1, :], shift_cm=state.shift_cm)


def channel_mix_full(p, cfg: ModelConfig, x, state: RWKVState
                     ) -> Tuple[jnp.ndarray, RWKVState]:
    xs = _token_shift(x, state.shift_cm)
    r = jax.nn.sigmoid(linear(p["rk"], _mix(p["mix"]["r"], x, xs)))
    k = linear(p["kk"], _mix(p["mix"]["k"], x, xs))
    y = r * linear(p["vv"], jnp.square(jax.nn.relu(k)))
    return y, state._replace(shift_cm=x[:, -1, :])


def channel_mix_decode(p, cfg: ModelConfig, x, state: RWKVState
                       ) -> Tuple[jnp.ndarray, RWKVState]:
    xs = state.shift_cm[:, None, :]
    r = jax.nn.sigmoid(linear(p["rk"], _mix(p["mix"]["r"], x, xs)))
    k = linear(p["kk"], _mix(p["mix"]["k"], x, xs))
    y = r * linear(p["vv"], jnp.square(jax.nn.relu(k)))
    return y, state._replace(shift_cm=x[:, -1, :])


def init_rwkv_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> RWKVState:
    rc = cfg.rwkv
    d = cfg.d_model
    h = d // rc.head_dim
    return RWKVState(
        wkv=jnp.zeros((batch, h, rc.head_dim, rc.head_dim), dtype),
        shift_tm=jnp.zeros((batch, d), dtype),
        shift_cm=jnp.zeros((batch, d), dtype),
    )
