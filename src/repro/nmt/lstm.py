"""2-layer BiLSTM encoder + attention LSTM decoder (paper model #1).

Mirrors the OpenNMT recipe the paper cites ([16]): bidirectional LSTM
encoder, unidirectional LSTM decoder with Luong (dot) global attention,
hidden size 500 on IWSLT'14 DE-EN.  Pure JAX, ``lax.scan`` recurrences —
the strict step dependency is exactly what makes T_exe linear in N and M
(paper §II-A).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.nmt.common import (
    RNNConfig,
    build_decode_from_states,
    build_encode_states,
    build_translate_batched,
    cross_entropy,
    dense,
    dense_params,
    embed_init,
    greedy_decode,
    lstm_cell,
    lstm_params,
    luong_attention,
    luong_attention_batch,
    masked_scan_rnn,
    scan_rnn,
)


class BiLSTMSeq2Seq:
    def __init__(self, cfg: RNNConfig):
        self.cfg = cfg

    # ------------------------------------------------------------- params
    def init(self, key) -> Dict:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 64))
        enc = []
        d_in = cfg.embed
        for _ in range(cfg.layers):
            enc.append({
                "fwd": lstm_params(next(keys), d_in, cfg.hidden),
                "bwd": lstm_params(next(keys), d_in, cfg.hidden),
                # project the 2H bidirectional output back to H
                "proj": dense_params(next(keys), 2 * cfg.hidden, cfg.hidden),
            })
            d_in = cfg.hidden
        dec = []
        d_in = cfg.embed
        for _ in range(cfg.layers):
            dec.append(lstm_params(next(keys), d_in, cfg.hidden))
            d_in = cfg.hidden
        return {
            "src_embed": embed_init(next(keys), cfg.vocab_src, cfg.embed),
            "tgt_embed": embed_init(next(keys), cfg.vocab_tgt, cfg.embed),
            "enc": enc,
            "dec": dec,
            "attn_combine": dense_params(next(keys), 2 * cfg.hidden, cfg.hidden),
            "out": dense_params(next(keys), cfg.hidden, cfg.vocab_tgt),
        }

    # ------------------------------------------------------------- encode
    def encode(self, params, src_tokens, src_mask=None):
        """src_tokens (N,) int32 -> enc_outs (N,H), decoder init carries.

        Batched (B,N) inputs take the masked-scan path: the recurrence
        freezes on padding steps (both directions), so each prefix-padded
        row's final states match its trimmed self; pad positions of
        ``enc_outs`` are zeros and masked out of attention downstream.
        """
        cfg = self.cfg
        x = params["src_embed"][src_tokens]
        if src_mask is None:
            src_mask = jnp.ones(src_tokens.shape, jnp.float32)
        if src_tokens.ndim == 2:
            b = src_tokens.shape[0]
            h0 = jnp.zeros((b, cfg.hidden))
            carries_for_dec = []
            for layer in params["enc"]:
                (hf, cf), outs_f = masked_scan_rnn(
                    lstm_cell, layer["fwd"], (h0, h0), x, src_mask)
                (hb, cb), outs_b = masked_scan_rnn(
                    lstm_cell, layer["bwd"], (h0, h0), x, src_mask,
                    reverse=True)
                x = dense(layer["proj"],
                          jnp.concatenate([outs_f, outs_b], axis=-1))
                x = jnp.tanh(x)
                carries_for_dec.append((0.5 * (hf + hb), 0.5 * (cf + cb)))
            return x, tuple(carries_for_dec), src_mask
        h0 = jnp.zeros((cfg.hidden,))
        carries_for_dec = []
        for layer in params["enc"]:
            (hf, cf), outs_f = scan_rnn(lstm_cell, layer["fwd"], (h0, h0), x)
            (hb, cb), outs_b = scan_rnn(lstm_cell, layer["bwd"], (h0, h0), x,
                                        reverse=True)
            x = dense(layer["proj"], jnp.concatenate([outs_f, outs_b], axis=-1))
            x = jnp.tanh(x)
            # decoder layer l starts from the mean of fwd/bwd final states
            carries_for_dec.append((0.5 * (hf + hb), 0.5 * (cf + cb)))
        return x, tuple(carries_for_dec), src_mask

    # -------------------------------------------------------- decode step
    def decode_step(self, params, state, token):
        """One autoregressive step.  state = (carries, enc_outs, enc_mask).

        Batch-polymorphic: with ``token`` (B,) and state carrying a
        leading batch dimension it advances all sequences at once (the
        compiled-scan decode path).
        """
        carries, enc_outs, enc_mask = state
        x = params["tgt_embed"][token]
        new_carries = []
        for layer_p, carry in zip(params["dec"], carries):
            carry, x = lstm_cell(layer_p, carry, x)
            new_carries.append(carry)
        attend = luong_attention_batch if jnp.ndim(token) else luong_attention
        ctx = attend(x, enc_outs, enc_mask)
        x = jnp.tanh(dense(params["attn_combine"],
                           jnp.concatenate([x, ctx], axis=-1)))
        logits = dense(params["out"], x)
        return (tuple(new_carries), enc_outs, enc_mask), logits

    # ---------------------------------------------------------- translate
    def make_translate(self, params):
        """Returns translate(src_tokens) -> (m_out, tokens), jit-backed."""
        encode = jax.jit(lambda s: self.encode(params, s))
        step = jax.jit(lambda st, tok: self.decode_step(params, st, tok))

        def translate(src_tokens, forced_len=None):
            enc_outs, carries, mask = encode(jnp.asarray(src_tokens))
            state = (carries, enc_outs, mask)
            return greedy_decode(step, state, self.cfg.max_decode_len,
                                 forced_len=forced_len)

        return translate

    def make_translate_batched(self, params, *, compiled: bool = True):
        """Batched translate: (B,N) [+ (B,N) mask] -> (lengths, tokens).

        ``compiled=True`` runs the single-dispatch scan fast path;
        ``compiled=False`` the per-sequence host loop (paper-faithful
        timing path).
        """
        def make_state(p, src, mask):
            enc_outs, carries, m = self.encode(p, src, mask)
            return (carries, enc_outs, m)

        return build_translate_batched(self, params, make_state,
                                       compiled=compiled)

    def make_encode_states(self, params):
        """Encode leg of a split placement: ships the decode-step state
        verbatim — (carries, annotation vectors (B,N,H), enc mask)."""
        def encode_data(src, mask):
            enc_outs, carries, m = self.encode(params, src, mask)
            return (carries, enc_outs, m)

        return build_encode_states(self, params, encode_data)

    def make_decode_from_states(self, params):
        """Decode leg: EncoderStates -> (lengths, tokens); shipped data
        is already the decode carry."""
        return build_decode_from_states(self, params, lambda data: data)

    # ------------------------------------------------------------- train
    def forward_teacher(self, params, src, src_mask, tgt_in):
        """Batched teacher-forced logits: (B,N),(B,N),(B,M) -> (B,M,V)."""
        def single(src_i, mask_i, tgt_i):
            enc_outs, carries, m = self.encode(params, src_i, mask_i)
            def step(carry_state, tok):
                state, _ = self.decode_step(params, carry_state, tok)
                return state, _
            state0 = (carries, enc_outs, m)
            _, logits = jax.lax.scan(
                lambda st, tok: self.decode_step(params, st, tok), state0, tgt_i
            )
            return logits
        return jax.vmap(single)(src, src_mask, tgt_in)

    def loss(self, params, batch):
        logits = self.forward_teacher(
            params, batch["src"], batch["src_mask"], batch["tgt_in"]
        )
        return cross_entropy(logits, batch["tgt_out"], batch["tgt_mask"])
