"""Marian-style encoder-decoder Transformer (paper model #3).

MarianMT ([20]) is a standard post-norm Transformer ("Attention Is All You
Need" base): sinusoidal positions, 6+6 layers, 8 heads.  The computational
profile the paper measures — parallel encoder (T ~ const in N for short
inputs on parallel hardware) vs strictly sequential masked-attention
decoding (T linear in M) — comes from this implementation's two paths:

* ``encode``      — one parallel pass over all N tokens;
* ``decode_step`` — one token at a time against a fixed-size KV cache
  (the production decode path; state carries per-layer K/V).

Both paths carry an optional leading BATCH dimension (2-D ``src_tokens``
/ 1-D ``token`` vectors) with per-sequence ``pos`` and prefix masks —
the compiled serving fast path (``make_translate_batched`` +
``batched_greedy_decode``) decodes a whole padded batch in one
``lax.scan``.

``attn_impl`` selects the attention backend for the batched paths:

* ``"xla"``    — plain einsum attention (default; XLA fuses it fine on
  CPU, and it is the bit-for-bit reference for the batched tests);
* ``"pallas"`` — routes the batched encoder and the teacher-forced
  decoder through :mod:`repro.kernels.flash_attention` and the cached
  decode step through :mod:`repro.kernels.decode_attention` (flash
  decode against the KV cache, lengths = pos+1 / source lengths).  On
  CPU the kernels run in interpret mode — validation of the production
  TPU path, not a CPU speedup.

The per-sequence (unbatched) methods keep the original einsum
implementation regardless of ``attn_impl`` — they are the
paper-faithful characterization path.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.nmt.common import (
    TransformerConfig,
    build_decode_from_states,
    build_encode_states,
    build_translate_batched,
    cross_entropy,
    dense,
    dense_params,
    embed_init,
    greedy_decode,
)


def sinusoidal(max_len: int, d_model: int):
    pos = jnp.arange(max_len)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d_model, 2)[None, :].astype(jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((max_len, d_model))
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle))
    return pe


def layer_norm(p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def ln_params(d):
    return {"g": jnp.ones((d,)), "b": jnp.zeros((d,))}


def mha_params(key, d_model):
    k = jax.random.split(key, 4)
    return {
        "q": dense_params(k[0], d_model, d_model),
        "k": dense_params(k[1], d_model, d_model),
        "v": dense_params(k[2], d_model, d_model),
        "o": dense_params(k[3], d_model, d_model),
    }


def _split_heads(x, heads):
    *lead, d = x.shape
    return x.reshape(*lead, heads, d // heads)


def mha(p, q_in, kv_in, heads, mask=None):
    """Full multi-head attention. q_in (Tq,D), kv_in (Tk,D)."""
    q = _split_heads(dense(p["q"], q_in), heads)        # (Tq,h,dh)
    k = _split_heads(dense(p["k"], kv_in), heads)
    v = _split_heads(dense(p["v"], kv_in), heads)
    dh = q.shape[-1]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(dh)
    if mask is not None:
        scores = jnp.where(mask[None, :, :] > 0, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", w, v)
    return dense(p["o"], out.reshape(q_in.shape[0], -1))


def ffn_params(key, d_model, d_ff):
    k1, k2 = jax.random.split(key)
    return {"in": dense_params(k1, d_model, d_ff),
            "out": dense_params(k2, d_ff, d_model)}


def ffn(p, x):
    return dense(p["out"], jax.nn.relu(dense(p["in"], x)))


class MarianTransformer:
    def __init__(self, cfg: TransformerConfig, attn_impl: str = "xla"):
        if attn_impl not in ("xla", "pallas"):
            raise ValueError(f"attn_impl must be 'xla'|'pallas', got {attn_impl!r}")
        self.cfg = cfg
        self.attn_impl = attn_impl
        self._pe = sinusoidal(max(cfg.max_src_len, cfg.max_decode_len) + 1,
                              cfg.d_model)

    # one (B,S,D) tensor -> (B,S,h,dh) heads view and back
    def _heads(self, x):
        b, s, d = x.shape
        return x.reshape(b, s, self.cfg.heads, d // self.cfg.heads)

    # ------------------------------------------------------------- params
    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 8 * (cfg.enc_layers + cfg.dec_layers) + 8))
        enc_layers = []
        for _ in range(cfg.enc_layers):
            enc_layers.append({
                "attn": mha_params(next(keys), cfg.d_model),
                "ln1": ln_params(cfg.d_model),
                "ffn": ffn_params(next(keys), cfg.d_model, cfg.d_ff),
                "ln2": ln_params(cfg.d_model),
            })
        dec_layers = []
        for _ in range(cfg.dec_layers):
            dec_layers.append({
                "self": mha_params(next(keys), cfg.d_model),
                "ln1": ln_params(cfg.d_model),
                "cross": mha_params(next(keys), cfg.d_model),
                "ln2": ln_params(cfg.d_model),
                "ffn": ffn_params(next(keys), cfg.d_model, cfg.d_ff),
                "ln3": ln_params(cfg.d_model),
            })
        return {
            "src_embed": embed_init(next(keys), cfg.vocab_src, cfg.d_model),
            "tgt_embed": embed_init(next(keys), cfg.vocab_tgt, cfg.d_model),
            "enc": enc_layers,
            "dec": dec_layers,
            "out": dense_params(next(keys), cfg.d_model, cfg.vocab_tgt),
        }

    # ------------------------------------------------------------- encode
    def encode(self, params, src_tokens, src_mask=None):
        """(N,) -> (enc_outs (N,D), mask); batched (B,N) -> ((B,N,D), (B,N)).

        The batched path expects prefix masks (real tokens first, padding
        after) — the serving batcher's discipline — and routes attention
        through the backend selected by ``attn_impl``.
        """
        if src_tokens.ndim == 2:
            return self._encode_batch(params, src_tokens, src_mask)
        cfg = self.cfg
        n = src_tokens.shape[0]
        if src_mask is None:
            src_mask = jnp.ones((n,), jnp.float32)
        x = params["src_embed"][src_tokens] * jnp.sqrt(float(cfg.d_model))
        x = x + self._pe[:n]
        attn_mask = src_mask[None, :] * jnp.ones((n, 1))
        for layer in params["enc"]:
            x = layer_norm(layer["ln1"], x + mha(layer["attn"], x, x,
                                                 cfg.heads, attn_mask))
            x = layer_norm(layer["ln2"], x + ffn(layer["ffn"], x))
        return x, src_mask

    def _attend_batch(self, p, q_in, kv_in, lengths, *, causal: bool):
        """Batched MHA with valid-key-prefix masking, on either backend.

        q_in (B,S,D), kv_in (B,T,D), lengths (B,) -> (B,S,D).
        """
        from repro.kernels import ops as kernel_ops

        q = self._heads(dense(p["q"], q_in))
        k = self._heads(dense(p["k"], kv_in))
        v = self._heads(dense(p["v"], kv_in))
        if self.attn_impl == "pallas":
            out = kernel_ops.flash_attention(q, k, v, lengths, causal=causal)
        else:
            dh = q.shape[-1]
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dh)
            t = kv_in.shape[1]
            valid = jnp.arange(t)[None, :] < lengths[:, None]     # (B,T)
            if causal:
                tri = jnp.tril(jnp.ones((q_in.shape[1], t), bool))
                keymask = valid[:, None, None, :] & tri[None, None, :, :]
            else:
                keymask = valid[:, None, None, :]
            s = jnp.where(keymask, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        b, sq = q_in.shape[0], q_in.shape[1]
        return dense(p["o"], out.reshape(b, sq, -1))

    def _encode_batch(self, params, src_tokens, src_mask):
        cfg = self.cfg
        b, n = src_tokens.shape
        if src_mask is None:
            src_mask = jnp.ones((b, n), jnp.float32)
        # >= 1 valid key per row: the attention kernels' contract (an
        # all-pad row then attends slot 0 only; its output is discarded)
        lengths = jnp.maximum(
            jnp.sum(src_mask > 0, axis=-1).astype(jnp.int32), 1)
        x = params["src_embed"][src_tokens] * jnp.sqrt(float(cfg.d_model))
        x = x + self._pe[:n]
        for layer in params["enc"]:
            a = self._attend_batch(layer["attn"], x, x, lengths, causal=False)
            x = layer_norm(layer["ln1"], x + a)
            x = layer_norm(layer["ln2"], x + ffn(layer["ffn"], x))
        return x, src_mask

    # ---------------------------------------------------- decoder w/ cache
    def init_cache(self, params, enc_outs, enc_mask):
        """Pre-compute cross-attention K/V; allocate fixed-size self K/V.

        Batched ``enc_outs`` (B,N,D) yield a batched cache: per-layer
        (B, max_decode_len, D) self K/V, per-sequence ``pos`` (B,).
        """
        cfg = self.cfg
        if enc_outs.ndim == 3:
            b = enc_outs.shape[0]
            layers = []
            for layer in params["dec"]:
                layers.append({
                    "k": jnp.zeros((b, cfg.max_decode_len, cfg.d_model)),
                    "v": jnp.zeros((b, cfg.max_decode_len, cfg.d_model)),
                    "xk": dense(layer["cross"]["k"], enc_outs),
                    "xv": dense(layer["cross"]["v"], enc_outs),
                })
            return {"layers": layers, "pos": jnp.zeros((b,), jnp.int32),
                    "enc_mask": enc_mask}
        layers = []
        for layer in params["dec"]:
            layers.append({
                "k": jnp.zeros((cfg.max_decode_len, cfg.d_model)),
                "v": jnp.zeros((cfg.max_decode_len, cfg.d_model)),
                "xk": dense(layer["cross"]["k"], enc_outs),
                "xv": dense(layer["cross"]["v"], enc_outs),
            })
        return {"layers": layers, "pos": jnp.asarray(0, jnp.int32),
                "enc_mask": enc_mask}

    def _cached_attn_batch(self, q, kh, vh, lengths):
        """One-query-token attention against a (B,T,D) cache.

        q (B,D), kh/vh (B,T,D), lengths (B,) = valid slots -> (B,D).
        ``attn_impl="pallas"`` routes through the flash-decode kernel.
        """
        from repro.kernels import ops as kernel_ops

        heads = self.cfg.heads
        b, t, d = kh.shape
        dh = d // heads
        qh = q.reshape(b, heads, dh)
        if self.attn_impl == "pallas":
            out = kernel_ops.flash_decode(
                qh, kh.reshape(b, t, heads, dh), vh.reshape(b, t, heads, dh),
                lengths)
            return out.reshape(b, d)
        s = jnp.einsum("bhd,bthd->bht", qh,
                       kh.reshape(b, t, heads, dh)) / jnp.sqrt(dh)
        valid = jnp.arange(t)[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bht,bthd->bhd", w,
                          vh.reshape(b, t, heads, dh)).reshape(b, d)

    def _decode_step_batch(self, params, state, token):
        """One decode step for a whole batch: token (B,) -> logits (B,V)."""
        cfg = self.cfg
        pos = state["pos"]                                    # (B,)
        enc_mask = state["enc_mask"]                          # (B,N)
        b = token.shape[0]
        bidx = jnp.arange(b)
        src_lens = jnp.maximum(
            jnp.sum(enc_mask > 0, axis=-1).astype(jnp.int32), 1)
        x = params["tgt_embed"][token] * jnp.sqrt(float(cfg.d_model))
        x = x + self._pe[pos]                                 # (B,D)
        new_layers = []
        for layer, cache in zip(params["dec"], state["layers"]):
            # self attention against the per-sequence KV cache
            k_new = dense(layer["self"]["k"], x)
            v_new = dense(layer["self"]["v"], x)
            ck = cache["k"].at[bidx, pos].set(k_new)
            cv = cache["v"].at[bidx, pos].set(v_new)
            a = self._cached_attn_batch(dense(layer["self"]["q"], x),
                                        ck, cv, pos + 1)
            x = layer_norm(layer["ln1"], x + dense(layer["self"]["o"], a))
            # cross attention against precomputed encoder K/V
            a = self._cached_attn_batch(dense(layer["cross"]["q"], x),
                                        cache["xk"], cache["xv"], src_lens)
            x = layer_norm(layer["ln2"], x + dense(layer["cross"]["o"], a))
            x = layer_norm(layer["ln3"], x + ffn(layer["ffn"], x))
            new_layers.append({"k": ck, "v": cv, "xk": cache["xk"],
                               "xv": cache["xv"]})
        logits = dense(params["out"], x)
        return ({"layers": new_layers, "pos": pos + 1,
                 "enc_mask": enc_mask}, logits)

    def decode_step(self, params, state, token):
        """One masked-attention step against the KV cache.

        ``token`` (B,) with a batched cache advances the whole batch in
        one step (per-sequence ``pos``); scalar ``token`` keeps the
        original per-sequence path.
        """
        if jnp.ndim(token) >= 1:
            return self._decode_step_batch(params, state, token)
        cfg = self.cfg
        heads = cfg.heads
        pos = state["pos"]
        x = params["tgt_embed"][token] * jnp.sqrt(float(cfg.d_model))
        x = x + self._pe[pos]
        new_layers = []
        valid = (jnp.arange(cfg.max_decode_len) <= pos).astype(jnp.float32)
        for layer, cache in zip(params["dec"], state["layers"]):
            # self attention against cache
            k_new = dense(layer["self"]["k"], x)
            v_new = dense(layer["self"]["v"], x)
            ck = cache["k"].at[pos].set(k_new)
            cv = cache["v"].at[pos].set(v_new)
            q = _split_heads(dense(layer["self"]["q"], x), heads)      # (h,dh)
            kh = _split_heads(ck, heads)                               # (T,h,dh)
            vh = _split_heads(cv, heads)
            dh = q.shape[-1]
            s = jnp.einsum("hd,thd->ht", q, kh) / jnp.sqrt(dh)
            s = jnp.where(valid[None, :] > 0, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("ht,thd->hd", w, vh).reshape(-1)
            x = layer_norm(layer["ln1"], x + dense(layer["self"]["o"], a))
            # cross attention against precomputed encoder K/V
            q = _split_heads(dense(layer["cross"]["q"], x), heads)
            kh = _split_heads(cache["xk"], heads)
            vh = _split_heads(cache["xv"], heads)
            s = jnp.einsum("hd,thd->ht", q, kh) / jnp.sqrt(dh)
            s = jnp.where(state["enc_mask"][None, :] > 0, s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("ht,thd->hd", w, vh).reshape(-1)
            x = layer_norm(layer["ln2"], x + dense(layer["cross"]["o"], a))
            x = layer_norm(layer["ln3"], x + ffn(layer["ffn"], x))
            new_layers.append({"k": ck, "v": cv, "xk": cache["xk"],
                               "xv": cache["xv"]})
        logits = dense(params["out"], x)
        return ({"layers": new_layers, "pos": pos + 1,
                 "enc_mask": state["enc_mask"]}, logits)

    # ---------------------------------------------------------- translate
    def make_translate(self, params):
        encode = jax.jit(lambda s: self.encode(params, s))
        step = jax.jit(lambda st, tok: self.decode_step(params, st, tok))

        def translate(src_tokens, forced_len=None):
            enc_outs, mask = encode(jnp.asarray(src_tokens))
            state = self.init_cache(params, enc_outs, mask)
            return greedy_decode(step, state, self.cfg.max_decode_len,
                                 forced_len=forced_len)

        return translate

    def make_translate_batched(self, params, *, compiled: bool = True):
        """Batched translate: (B,N) [+ (B,N) mask] -> (lengths, tokens).

        ``compiled=True`` is the scan fast path — encoder, cache init and
        the whole greedy decode compile into ONE dispatch per (B, N)
        shape; ``compiled=False`` is the per-sequence host loop whose
        wall-clock stays linear in M (the Fig. 2a timing path).
        """
        def make_state(p, src, mask):
            enc_outs, m = self.encode(p, src, mask)
            return self.init_cache(p, enc_outs, m)

        return build_translate_batched(self, params, make_state,
                                       compiled=compiled)

    def make_encode_states(self, params):
        """Encode leg of a split placement: ships only the encoder
        memory (B,N,D) + mask — NOT the decoder cache.  The cross-
        attention K/V projections use *decoder* parameters, so they are
        rebuilt on the decode tier (see ``make_decode_from_states``),
        keeping the wire payload at n x d_model as the scheduler's
        `ActivationCostModel` prices it."""
        return build_encode_states(
            self, params,
            lambda src, mask: self.encode(params, src, mask))

    def make_decode_from_states(self, params):
        """Decode leg: rebuilds the KV cache (cross K/V projections +
        empty self K/V) from the shipped memory, then runs the exact
        compiled scan decode of the fused path."""
        def state_from_data(data):
            enc_outs, m = data
            return self.init_cache(params, enc_outs, m)

        return build_decode_from_states(self, params, state_from_data)

    # -------------------------------------------------------------- train
    def forward_teacher(self, params, src, src_mask, tgt_in):
        """Batched parallel (causally-masked) teacher-forced logits.

        With ``attn_impl="pallas"`` the whole stack (encoder self-attn,
        decoder causal self-attn, cross-attn) runs through the flash
        kernel; the default is the vmapped einsum reference.
        """
        cfg = self.cfg
        if self.attn_impl == "pallas":
            enc_outs, m = self._encode_batch(params, src, src_mask)
            src_lens = jnp.maximum(
                jnp.sum(m > 0, axis=-1).astype(jnp.int32), 1)
            b, t = tgt_in.shape
            tgt_lens = jnp.full((b,), t, jnp.int32)
            x = params["tgt_embed"][tgt_in] * jnp.sqrt(float(cfg.d_model))
            x = x + self._pe[:t]
            for layer in params["dec"]:
                a = self._attend_batch(layer["self"], x, x, tgt_lens,
                                       causal=True)
                x = layer_norm(layer["ln1"], x + a)
                a = self._attend_batch(layer["cross"], x, enc_outs,
                                       src_lens, causal=False)
                x = layer_norm(layer["ln2"], x + a)
                x = layer_norm(layer["ln3"], x + ffn(layer["ffn"], x))
            return dense(params["out"], x)

        def single(src_i, mask_i, tgt_i):
            enc_outs, m = self.encode(params, src_i, mask_i)
            t = tgt_i.shape[0]
            x = params["tgt_embed"][tgt_i] * jnp.sqrt(float(cfg.d_model))
            x = x + self._pe[:t]
            causal = jnp.tril(jnp.ones((t, t)))
            cross_m = m[None, :] * jnp.ones((t, 1))
            for layer in params["dec"]:
                x = layer_norm(layer["ln1"],
                               x + mha(layer["self"], x, x, cfg.heads, causal))
                x = layer_norm(layer["ln2"],
                               x + mha(layer["cross"], x, enc_outs, cfg.heads,
                                       cross_m))
                x = layer_norm(layer["ln3"], x + ffn(layer["ffn"], x))
            return dense(params["out"], x)

        return jax.vmap(single)(src, src_mask, tgt_in)

    def loss(self, params, batch):
        logits = self.forward_teacher(
            params, batch["src"], batch["src_mask"], batch["tgt_in"]
        )
        return cross_entropy(logits, batch["tgt_out"], batch["tgt_mask"])
