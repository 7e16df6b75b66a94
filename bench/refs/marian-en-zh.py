"""Plain reference for the Marian encoder-decoder, in float32 jax.numpy.

A post-norm Transformer base as the program serves it: sinusoidal
positions added to embeddings scaled by sqrt(d_model); per layer,
attention (or self-, then cross-attention in the decoder) and a ReLU
feed-forward, each followed by residual add and LayerNorm (eps 1e-5);
biases on every projection; separate source, target and output
matrices.  Departures from the published opus-mt-en-zh checkpoint are
the program's, listed in the config file: ReLU for swish, and no tied
embeddings.

No kernels, no cache, no batching tricks: full attention with masks,
the decoder teacher-forced over [BOS, served tokens] in one pass.  Reads
its weights from ``bench.weights`` by path, never from the program.

``precision`` is "highest" for the reference (float32 matmuls at full
precision, on the served bfloat16 weight values) or "float8" for the
control, one step below the configuration's bfloat16 weights: every
matmul operand (weights and activations) quantized to float8_e4m3fn with
a scale per tensor.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

BOS_ID = 1
E4M3_MAX = 448.0


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _exact(a):
    return a


def _sinusoidal(n: int, d: int) -> np.ndarray:
    pos = np.arange(n, dtype=np.float32)[:, None]
    dim = np.arange(0, d, 2, dtype=np.float32)[None, :]
    angle = pos / np.power(np.float32(10000.0), dim / np.float32(d))
    pe = np.zeros((n, d), np.float32)
    pe[:, 0::2] = np.sin(angle)
    pe[:, 1::2] = np.cos(angle)
    return pe


def _layer_norm(w, pre, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w[pre + "/g"] + w[pre + "/b"]


def forward(w: Dict, cfg: Dict, src, src_len, tgt_in, q=_exact):
    """Teacher-forced logits (B, T, V) for tgt_in (B,T); ``q`` is applied
    to every matmul operand."""
    d, heads = cfg["d_model"], cfg["encoder_attention_heads"]

    def _dense(w, pre, x):
        return q(x) @ q(w[pre + "/w"]) + w[pre + "/b"]

    def _attention(w, pre, xq, xkv, keymask, heads):
        """xq (B,Tq,D), xkv (B,Tk,D), keymask (B,Tq,Tk) bool."""
        b, tq, d = xq.shape
        dh = d // heads
        split = lambda t: t.reshape(b, t.shape[1], heads, dh)  # noqa: E731
        qh = split(_dense(w, pre + "/q", xq))
        k = split(_dense(w, pre + "/k", xkv))
        v = split(_dense(w, pre + "/v", xkv))
        s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(k)) / math.sqrt(dh)
        s = jnp.where(keymask[:, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", q(p), q(v)).reshape(b, tq, d)
        return _dense(w, pre + "/o", o)

    def _ffn(w, pre, x):
        return _dense(w, pre + "/out", jax.nn.relu(_dense(w, pre + "/in", x)))

    scale = math.sqrt(d)
    pe = jnp.asarray(_sinusoidal(max(src.shape[1], tgt_in.shape[1]), d))
    b, n = src.shape
    t = tgt_in.shape[1]
    valid = jnp.arange(n)[None, :] < src_len[:, None]            # (B,N)
    x = w["src_embed"][src] * scale + pe[:n]
    enc_mask = jnp.broadcast_to(valid[:, None, :], (b, n, n))
    for i in range(cfg["encoder_layers"]):
        p = f"enc/{i}"
        x = _layer_norm(w, p + "/ln1",
                        x + _attention(w, p + "/attn", x, x, enc_mask, heads))
        x = _layer_norm(w, p + "/ln2", x + _ffn(w, p + "/ffn", x))
    mem = x
    y = w["tgt_embed"][tgt_in] * scale + pe[:t]
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool))[None], (b, t, t))
    cross = jnp.broadcast_to(valid[:, None, :], (b, t, n))
    for i in range(cfg["decoder_layers"]):
        p = f"dec/{i}"
        y = _layer_norm(w, p + "/ln1",
                        y + _attention(w, p + "/self", y, y, causal, heads))
        y = _layer_norm(w, p + "/ln2",
                        y + _attention(w, p + "/cross", y, mem, cross, heads))
        y = _layer_norm(w, p + "/ln3", y + _ffn(w, p + "/ffn", y))
    return _dense(w, "out", y)


class Reference:
    """Logits of the served positions, for requests of one run."""

    def __init__(self, cfg: Dict, spec: weights.Spec, seed: int):
        self.cfg = cfg
        self.w = weights.LayerDrawer(spec, "")(seed)
        self._fwd = {
            name: jax.jit(lambda w, s, l, t, q=q: forward(w, cfg, s, l, t, q))
            for name, q in (("highest", _exact), ("float8", _fp8))}

    def logits(self, prompts: Sequence[np.ndarray],
               served: Sequence[np.ndarray],
               precision: str = "highest") -> List[np.ndarray]:
        """For request i, logits (M_i, V) that predict served[i]."""
        # one padded shape per run: one compile
        n = -(-max(len(p) for p in prompts) // 128) * 128
        t = -(-max(len(s) for s in served) // 128) * 128
        k = len(prompts)
        src = np.zeros((k, n), np.int32)
        tgt = np.zeros((k, t), np.int32)
        for i, (p, s) in enumerate(zip(prompts, served)):
            src[i, :len(p)] = p
            tgt[i, 0] = BOS_ID
            tgt[i, 1:len(s)] = s[:-1]
        lens = np.asarray([len(p) for p in prompts], np.int32)
        if precision not in self._fwd:
            raise ValueError(f"unknown precision {precision!r}")
        with jax.default_matmul_precision("highest"):
            out = np.asarray(self._fwd[precision](self.w, src, lens, tgt))
        return [out[i, :len(s)] for i, s in enumerate(served)]
