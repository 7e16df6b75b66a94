"""Public jit'd wrappers for the Pallas kernels.

Off the TPU the kernels execute in interpret mode — the kernel *body*
runs in Python per grid cell, which validates the tiling and carry
logic; on TPU the same `pl.pallas_call` lowers to Mosaic (what Mosaic
accepts is pinned by tests/test_tpu_compile.py).  Wrappers handle
padding to block multiples and auto-select interpret mode off the
default backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import rwkv6_wkv as _wkv
from repro.kernels import ssd_scan as _ssd


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if not pad:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, lengths=None, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q (B,S,H,D); k/v (B,T,Hkv,D) -> (B,S,H,D). Pads S/T to blocks.

    ``lengths`` (B,) int32 marks each sequence's valid KEY prefix — the
    padded-batch discipline of the batched NMT/serving paths.  When None
    every real key position is valid; block-padding tail keys are masked
    either way, so non-causal callers no longer need to pre-pad.
    """
    interpret = _auto_interpret(interpret)
    s, t = q.shape[1], k.shape[1]
    bq = min(block_q, max(8, 1 << (s - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (t - 1).bit_length()))
    qp, pad_q = _pad_to(q, 1, bq)
    kp, pad_k = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    if lengths is None:
        lengths = jnp.full((q.shape[0],), t, jnp.int32)
    out = _fa.flash_attention(qp, kp, vp, causal=causal,
                              lengths=jnp.asarray(lengths, jnp.int32),
                              block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :s] if pad_q or pad_k else out


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q, k_cache, v_cache, lengths, *, block_s: int = 256,
                 interpret: bool | None = None):
    """q (B,H,D); caches (B,S,Hkv,D); lengths (B,) -> (B,H,D)."""
    interpret = _auto_interpret(interpret)
    s = k_cache.shape[1]
    bs = min(block_s, max(8, 1 << (s - 1).bit_length()))
    kp, _ = _pad_to(k_cache, 1, bs)
    vp, _ = _pad_to(v_cache, 1, bs)
    return _da.flash_decode(q, kp, vp, lengths, block_s=bs,
                            interpret=interpret)


def _fit_chunk(seq: int, chunk: int) -> int:
    """Chunk no longer than the (8-aligned) sequence: TPU row tiles are
    multiples of 8, so the scans pad the sequence to a chunk multiple."""
    return min(chunk, -(-seq // 8) * 8)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, log_w, u, s0=None, *, chunk: int = 32,
              interpret: bool | None = None):
    """Chunked WKV6. Shapes as in repro.kernels.ref.rwkv6_ref.

    Any sequence length: the tail is padded with k=0, log_w=0 steps,
    which leave the state untouched, and their outputs are dropped.
    """
    interpret = _auto_interpret(interpret)
    s = r.shape[1]
    chunk = _fit_chunk(s, chunk)
    r, k, v, log_w = (_pad_to(x, 1, chunk)[0] for x in (r, k, v, log_w))
    y, s_t = _wkv.rwkv6_wkv(r, k, v, log_w, u, s0, chunk=chunk,
                            interpret=interpret)
    return y[:, :s], s_t


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b_in, c_in, s0=None, *, chunk: int = 64,
             interpret: bool | None = None):
    """Chunked Mamba2 SSD. Shapes as in repro.kernels.ref.ssd_ref.

    Any sequence length: the tail is padded with dt=0 steps (no decay,
    no input), which leave the state untouched, and their outputs are
    dropped.
    """
    interpret = _auto_interpret(interpret)
    s = x.shape[1]
    chunk = _fit_chunk(s, chunk)
    x, dt, b_in, c_in = (_pad_to(t, 1, chunk)[0] for t in (x, dt, b_in, c_in))
    y, s_t = _ssd.ssd_scan(x, dt, a_log, b_in, c_in, s0, chunk=chunk,
                           interpret=interpret)
    return y[:, :s], s_t
