"""Attention mixers: GQA (full / decode-vs-cache), sliding window, cross
attention, and DeepSeek-style MLA with the compressed-KV decode path.

Shapes: activations (B, S, D); KV caches (B, S_max, H_kv, Dh); MLA cache
is the *compressed* latent (B, S_max, kv_lora_rank + qk_rope_head_dim) —
that compression is MLA's contribution (DeepSeek-V2/V3) and is what makes
its long-context decode memory traffic ~1/28th of dense GQA.

All masks are built from position arithmetic (no (S,S) bool materialized
for decode). The jnp paths here are the lowering targets for the dry-run;
``repro.kernels`` holds the Pallas TPU versions validated against
``repro.kernels.ref`` (same math).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import MLAConfig, ModelConfig
from repro.models.layers.basic import (
    apply_rope,
    head_rmsnorm,
    linear,
    linear_params,
    rmsnorm,
    rmsnorm_params,
)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ====================================================================== GQA
def gqa_params(key, cfg: ModelConfig, cross: bool = False, dtype=jnp.float32):
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "q": linear_params(ks[0], d, h * dh, dtype),
        "k": linear_params(ks[1], d, hkv * dh, dtype),
        "v": linear_params(ks[2], d, hkv * dh, dtype),
        "o": linear_params(ks[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"g": jnp.ones((dh,), jnp.float32)}
        p["k_norm"] = {"g": jnp.ones((dh,), jnp.float32)}
    if cross:
        p["xq"] = linear_params(ks[4], d, h * dh, dtype)
        p["xk"] = linear_params(ks[5], d, hkv * dh, dtype)
        p["xv"] = linear_params(ks[6], d, hkv * dh, dtype)
        p["xo"] = linear_params(ks[7], h * dh, d, dtype)
    return p


def _qkv(p, cfg: ModelConfig, x, positions, prefix=""):
    b, s, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p[prefix + "q"], x).reshape(b, s, h, dh)
    k = linear(p[prefix + "k"], x).reshape(b, s, hkv, dh)
    v = linear(p[prefix + "v"], x).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = head_rmsnorm(p["q_norm"]["g"], q, cfg.norm_eps)
        k = head_rmsnorm(p["k_norm"]["g"], k, cfg.norm_eps)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def sdpa(q, k, v, mask, scale: Optional[float] = None):
    """Grouped scaled-dot-product attention (materialized scores).

    q (B,S,H,Dh), k/v (B,T,Hkv,Dh), mask (B,S,T) bool (True=keep).
    Used on SHORT query lengths only (decode S=1, tiny tests); long
    sequences go through :func:`blocked_sdpa`.
    """
    b, s, h, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(b, s, hkv, rep, dh)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, k) * jnp.asarray(scale, q.dtype)
    scores = jnp.where(mask[:, None, None, :, :], scores.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrst,btgd->bsgrd", w, v)
    return out.reshape(b, s, h, dh)


DEFAULT_Q_BLOCK = 512


def blocked_sdpa(q, k, v, *, causal: bool = True,
                 window: Optional[int] = None, kv_mask=None,
                 q_block: int = DEFAULT_Q_BLOCK, scale: Optional[float] = None):
    """Memory-bounded attention: scan over query blocks, remat per block.

    Never materializes (S,T) score tensors — per step only
    (B, q_block, H, T) lives, and jax.checkpoint on the body makes the
    backward recompute it (flash-attention's memory discipline expressed
    in HLO; the Pallas kernel in repro.kernels is the TPU-tiled version
    of the same schedule).

    q (B,S,H,Dh); k/v (B,T,Hkv,Dh); kv_mask (B,T) optional (cross-attn).
    Query positions are the LAST S positions of the T-long key axis
    (offset = T - S), which covers self-attention (T=S) and decode-tail
    use alike.
    """
    b, s, h, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else dh ** -0.5
    l = min(q_block, s)
    pad = (-s) % l
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = q.shape[1] // l
    qb = q.reshape(b, nb, l, hkv, rep, dh)
    offset = t - s
    kpos = jnp.arange(t)[None, :]

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(qblk, blk_idx):
        scores = jnp.einsum("blgrd,btgd->bgrlt", qblk, k) \
            * jnp.asarray(scale, q.dtype)
        scores = scores.astype(jnp.float32)
        qpos = blk_idx * l + jnp.arange(l)[:, None] + offset   # (l,1)
        mask = jnp.ones((l, t), bool)
        if causal:
            mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        if kv_mask is not None:
            scores = jnp.where(kv_mask[:, None, None, None, :] > 0,
                               scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bgrlt,btgd->blgrd", w, v)

    def scan_body(_, inp):
        qblk, idx = inp
        return (), body(qblk, idx)

    _, out = jax.lax.scan(scan_body, (),
                          (jnp.moveaxis(qb, 1, 0), jnp.arange(nb)))
    dv = v.shape[-1]                      # may differ from q's head dim (MLA)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nb * l, h, dv)
    return out[:, :s]


def attn_full(p, cfg: ModelConfig, x, *, window: Optional[int] = None,
              causal: bool = True, positions=None,
              q_block: int = DEFAULT_Q_BLOCK):
    """Full-sequence attention (train / prefill). Returns (y, (k, v))."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(p, cfg, x, positions)
    y = blocked_sdpa(q, k, v, causal=causal, window=window, q_block=q_block)
    y = linear(p["o"], y.reshape(b, s, -1))
    return y, (k, v)


def attn_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos, *,
                window: Optional[int] = None, ring: bool = False):
    """One-token decode against a fixed-size cache.

    x (B,1,D); cache_k/v (B,S_max,Hkv,Dh); pos (B,) is the ABSOLUTE token
    position (drives RoPE).  Two cache disciplines:

    * linear (ring=False): slot == position; optional sliding ``window``
      masks out slots older than pos-window.
    * ring (ring=True): cache holds exactly the last S_max tokens, the
      write slot is pos % S_max, and once pos >= S_max every slot is valid
      history.  This is the 500k-context SWA cache: memory O(window), not
      O(context).
    """
    b, _, _ = x.shape
    s_max = cache_k.shape[1]
    positions = pos[:, None]                                  # (B,1)
    q, k, v = _qkv(p, cfg, x, positions)
    write_idx = pos % s_max if ring else pos
    oh = jax.nn.one_hot(write_idx, s_max, dtype=cache_k.dtype)  # (B,S_max)
    cache_k = cache_k * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * k
    cache_v = cache_v * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * v
    idx = jnp.arange(s_max)[None, :]                          # (1,S_max)
    if ring:
        mask = (idx <= pos[:, None]) | (pos[:, None] >= s_max)
    else:
        mask = idx <= pos[:, None]
        if window is not None:
            mask &= idx > (pos[:, None] - window)
    y = sdpa(q, cache_k, cache_v, mask[:, None, :])
    y = linear(p["o"], y.reshape(b, 1, -1))
    return y, cache_k, cache_v


def cross_attn(p, cfg: ModelConfig, x, enc_k, enc_v, enc_mask):
    """Decoder->encoder attention. enc_k/v (B,T,Hkv,Dh) precomputed."""
    b, s, _ = x.shape
    h, dh = cfg.num_heads, cfg.head_dim
    q = linear(p["xq"], x).reshape(b, s, h, dh)
    y = blocked_sdpa(q, enc_k, enc_v, causal=False, kv_mask=enc_mask)
    return linear(p["xo"], y.reshape(b, s, -1))


def encode_cross_kv(p, cfg: ModelConfig, enc_out):
    b, t, _ = enc_out.shape
    hkv, dh = cfg.num_kv_heads, cfg.head_dim
    k = linear(p["xk"], enc_out).reshape(b, t, hkv, dh)
    v = linear(p["xv"], enc_out).reshape(b, t, hkv, dh)
    return k, v


def attn_decode_seq_sharded(p, cfg: ModelConfig, x, cache_k, cache_v, pos,
                            *, mesh, seq_axis: str, batch_axes):
    """Flash-decode over a sequence-sharded cache via shard_map.

    Each ``seq_axis`` shard updates/attends only its local cache slice and
    the shards exchange softmax statistics (running max, normalizer,
    weighted accumulator — O(B,H,Dh) per layer) instead of the baseline's
    cache/score all-gathers.  This is the TPU-native analog of
    flash-decode's split-K reduction, expressed with lax collectives.
    """
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    s_max = cache_k.shape[1]
    hkv, h, dh = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    rep = h // hkv
    positions = pos[:, None]
    q, k_new, v_new = _qkv(p, cfg, x, positions)      # q (B,1,H,Dh)
    nshards = mesh.shape[seq_axis]
    s_loc = s_max // nshards
    bspec = batch_axes if batch_axes else None

    def body(q_l, kn, vn, ck, cv, pos_l):
        # local shapes: ck/cv (B_l, s_loc, Hkv, Dh); q_l (B_l,1,H,Dh)
        i = jax.lax.axis_index(seq_axis)
        base = i * s_loc
        local = pos_l - base
        in_range = (local >= 0) & (local < s_loc)
        oh = (jax.nn.one_hot(jnp.clip(local, 0, s_loc - 1), s_loc,
                             dtype=ck.dtype)
              * in_range[:, None].astype(ck.dtype))
        ck = ck * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * kn
        cv = cv * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * vn

        bl = q_l.shape[0]
        qg = q_l.reshape(bl, 1, hkv, rep, dh)
        scores = jnp.einsum("bsgrd,btgd->bgrst", qg, ck) \
            * jnp.asarray(dh ** -0.5, q_l.dtype)       # (B,g,r,1,s_loc)
        idx = base + jnp.arange(s_loc)[None, :]
        mask = idx <= pos_l[:, None]
        scores = jnp.where(mask[:, None, None, None, :],
                           scores.astype(jnp.float32), NEG_INF)
        m_loc = scores.max(axis=-1)                    # (B,g,r,1)
        pexp = jnp.exp(scores - m_loc[..., None])
        pexp = jnp.where(mask[:, None, None, None, :], pexp, 0.0)
        l_loc = pexp.sum(axis=-1)
        o_loc = jnp.einsum("bgrst,btgd->bgrsd",
                           pexp.astype(ck.dtype), cv)  # (B,g,r,1,Dh)
        # combine split-cache softmax stats across the seq shards
        m_g = jax.lax.pmax(m_loc, seq_axis)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, seq_axis)
        o = jax.lax.psum(o_loc * corr[..., None].astype(o_loc.dtype),
                         seq_axis)
        o = o / jnp.maximum(l_g, 1e-30)[..., None].astype(o_loc.dtype)
        return o.reshape(bl, 1, h, dh), ck, cv

    y, ck, cv = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None),
                  P(bspec, seq_axis, None, None),
                  P(bspec, seq_axis, None, None), P(bspec)),
        out_specs=(P(bspec, None, None, None),
                   P(bspec, seq_axis, None, None),
                   P(bspec, seq_axis, None, None)),
        check_vma=False,
    )(q, k_new, v_new, cache_k, cache_v, pos)
    y = linear(p["o"], y.reshape(x.shape[0], 1, -1))
    return y, ck, cv


# ====================================================================== MLA
def mla_params(key, cfg: ModelConfig, dtype=jnp.float32):
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 8)
    return {
        "q_down": linear_params(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": rmsnorm_params(m.q_lora_rank),
        "q_up": linear_params(ks[1], m.q_lora_rank,
                              h * (m.qk_nope_head_dim + m.qk_rope_head_dim),
                              dtype),
        "kv_down": linear_params(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim,
                                 dtype),
        "kv_norm": rmsnorm_params(m.kv_lora_rank),
        "k_up": linear_params(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim,
                              dtype),
        "v_up": linear_params(ks[4], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "o": linear_params(ks[5], h * m.v_head_dim, d, dtype),
    }


def _mla_q(p, cfg, x, positions):
    m, h = cfg.mla, cfg.num_heads
    b, s, _ = x.shape
    cq = rmsnorm(p["q_norm"], linear(p["q_down"], x), cfg.norm_eps)
    q = linear(p["q_up"], cq).reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _mla_latent(p, cfg, x, positions):
    """Compressed KV latent: c_kv (B,S,rank) + rotated shared k_pe (B,S,dr)."""
    m = cfg.mla
    ckv_full = linear(p["kv_down"], x)
    c_kv, k_pe = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(p["kv_norm"], c_kv, cfg.norm_eps)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_pe


def mla_full(p, cfg: ModelConfig, x, *, positions=None):
    """Full-sequence MLA (train/prefill), expanded form. Returns (y, cache).

    cache = (c_kv, k_pe): the compressed latent is what gets cached —
    per token it is kv_lora_rank + qk_rope_head_dim floats vs
    2*H*Dh for dense GQA (DeepSeek-V3's ~28x KV reduction).
    """
    m, h = cfg.mla, cfg.num_heads
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q_nope, q_pe = _mla_q(p, cfg, x, positions)
    c_kv, k_pe = _mla_latent(p, cfg, x, positions)
    k_nope = linear(p["k_up"], c_kv).reshape(b, s, h, m.qk_nope_head_dim)
    v = linear(p["v_up"], c_kv).reshape(b, s, h, m.v_head_dim)
    # fold the shared rope key into per-head effective q/k so the blocked
    # (flash-style) path applies unchanged: scores = q_eff · k_eff
    q_eff = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = blocked_sdpa(q_eff, k_eff, v, causal=True, scale=scale)
    y = y.reshape(b, s, -1)
    return linear(p["o"], y), (c_kv, k_pe)


def mla_decode(p, cfg: ModelConfig, x, cache_ckv, cache_kpe, pos):
    """One-token MLA decode in the *absorbed* formulation.

    Attention runs directly in the compressed latent space: q_nope is
    absorbed through k_up (q_c = q_nope @ W_uk per head), scores are taken
    against the cached latent, and the weighted latent is expanded through
    v_up once per step. Per-step HBM traffic is the latent cache
    (rank+dr ~ 576 floats/token) instead of 2*H*Dh (=32768 for V3).
    """
    m, h = cfg.mla, cfg.num_heads
    b = x.shape[0]
    s_max = cache_ckv.shape[1]
    positions = pos[:, None]
    q_nope, q_pe = _mla_q(p, cfg, x, positions)           # (B,1,H,·)
    c_kv_new, k_pe_new = _mla_latent(p, cfg, x, positions)
    oh = jax.nn.one_hot(pos, s_max, dtype=cache_ckv.dtype)
    cache_ckv = cache_ckv * (1 - oh)[:, :, None] + oh[:, :, None] * c_kv_new
    cache_kpe = cache_kpe * (1 - oh)[:, :, None] + oh[:, :, None] * k_pe_new
    # absorb q through W_uk: (B,H,rank)
    w_kup = p["k_up"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kup.astype(x.dtype))
    scale = 1.0 / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = (jnp.einsum("bhr,btr->bht", q_c, cache_ckv)
              + jnp.einsum("bhd,btd->bht", q_pe[:, 0], cache_kpe)) * scale
    mask = jnp.arange(s_max)[None, :] <= pos[:, None]
    scores = jnp.where(mask[:, None, :], scores.astype(jnp.float32), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    lat = jnp.einsum("bht,btr->bhr", w, cache_ckv)        # (B,H,rank)
    w_vup = p["v_up"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    y = jnp.einsum("bhr,rhd->bhd", lat, w_vup.astype(x.dtype)).reshape(b, 1, -1)
    return linear(p["o"], y), cache_ckv, cache_kpe
