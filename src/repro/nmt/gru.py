"""Single-layer GRU encoder/decoder, hidden 256 (paper model #2).

The paper's FR-EN model ([18]): a minimal seq2seq without attention —
the encoder's final hidden state is the fixed-size context handed to the
decoder (the classic "context vector" architecture of Fig. 1a).
"""

from __future__ import annotations

from typing import Dict

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
    gru_cell,
    gru_params,
    masked_scan_rnn,
    scan_rnn,
)


class GRUSeq2Seq:
    def __init__(self, cfg: RNNConfig):
        self.cfg = cfg

    def init(self, key) -> Dict:
        cfg = self.cfg
        k = iter(jax.random.split(key, 16))
        return {
            "src_embed": embed_init(next(k), cfg.vocab_src, cfg.embed),
            "tgt_embed": embed_init(next(k), cfg.vocab_tgt, cfg.embed),
            "enc": gru_params(next(k), cfg.embed, cfg.hidden),
            "dec": gru_params(next(k), cfg.embed, cfg.hidden),
            "out": dense_params(next(k), cfg.hidden, cfg.vocab_tgt),
        }

    def encode(self, params, src_tokens, src_mask=None):
        """(N,) -> context (H,); or batched (B,N) [+ mask] -> (B,H).

        The batched path freezes the recurrence on padding steps, so a
        prefix-padded row yields the same context as its trimmed self.
        """
        x = params["src_embed"][src_tokens]
        if src_tokens.ndim == 2:
            b = src_tokens.shape[0]
            if src_mask is None:
                src_mask = jnp.ones(src_tokens.shape, jnp.float32)
            h0 = jnp.zeros((b, self.cfg.hidden))
            h, _ = masked_scan_rnn(gru_cell, params["enc"], h0, x, src_mask)
            return h
        h0 = jnp.zeros((self.cfg.hidden,))
        h, _ = scan_rnn(gru_cell, params["enc"], h0, x)
        return h  # fixed-size context = final hidden state

    def decode_step(self, params, state, token):
        """One step; batch-polymorphic (state (H,)+scalar or (B,H)+(B,))."""
        x = params["tgt_embed"][token]
        h, _ = gru_cell(params["dec"], state, x)
        return h, dense(params["out"], h)

    def make_translate(self, params):
        encode = jax.jit(lambda s: self.encode(params, s))
        step = jax.jit(lambda st, tok: self.decode_step(params, st, tok))

        def translate(src_tokens, forced_len=None):
            h = encode(jnp.asarray(src_tokens))
            return greedy_decode(step, h, self.cfg.max_decode_len,
                                 forced_len=forced_len)

        return translate

    def make_translate_batched(self, params, *, compiled: bool = True):
        """Batched translate: (B,N) [+ (B,N) mask] -> (lengths, tokens).

        ``compiled=True`` is the scan fast path (one XLA dispatch per
        call); ``compiled=False`` the paper-faithful per-sequence host
        loop (timing path).
        """
        return build_translate_batched(
            self, params,
            lambda p, src, mask: self.encode(p, src, mask),
            compiled=compiled)

    def make_encode_states(self, params):
        """Encode leg of a split placement: (B,N) [+ mask] ->
        :class:`EncoderStates` carrying the final hidden state (B,H) —
        the GRU's fixed-size context is the whole payload."""
        return build_encode_states(
            self, params,
            lambda src, mask: self.encode(params, src, mask))

    def make_decode_from_states(self, params):
        """Decode leg: EncoderStates -> (lengths, tokens); the shipped
        hidden state IS the decode carry, no rebuild needed."""
        return build_decode_from_states(self, params, lambda data: data)

    def forward_teacher(self, params, src, src_mask, tgt_in):
        def single(src_i, mask_i, tgt_i):
            h = self.encode(params, src_i, mask_i)
            _, logits = jax.lax.scan(
                lambda st, tok: self.decode_step(params, st, tok), h, tgt_i
            )
            return logits
        return jax.vmap(single)(src, src_mask, tgt_in)

    def loss(self, params, batch):
        logits = self.forward_teacher(
            params, batch["src"], batch["src_mask"], batch["tgt_in"]
        )
        return cross_entropy(logits, batch["tgt_out"], batch["tgt_mask"])
