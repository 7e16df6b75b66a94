"""Shared building blocks for the paper-faithful seq2seq models.

Two greedy-decode paths live here, with opposite goals:

* :func:`greedy_decode` — the HOST loop: one jitted step dispatch per
  token.  Its wall-clock is linear in M by construction, which is the
  paper-faithful timing path (§II-A, Fig. 2a) used by the offline
  characterization sweeps.
* :func:`batched_greedy_decode` — the COMPILED fast path: a single
  ``jax.lax.scan`` over decode steps with a leading batch dimension and
  on-device EOS ``done`` masking, i.e. ONE XLA dispatch per translate
  call instead of one per token.  This is what serving uses; the host
  loop stays behind the ``compiled=False`` flag of the models'
  ``make_translate_batched`` wrappers for timing studies.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import BOS_ID, EOS_ID, PAD_ID


@dataclasses.dataclass(frozen=True)
class RNNConfig:
    vocab_src: int = 8000
    vocab_tgt: int = 8000
    embed: int = 256
    hidden: int = 256
    layers: int = 1
    max_decode_len: int = 256


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_src: int = 8000
    vocab_tgt: int = 8000
    d_model: int = 256
    heads: int = 8
    d_ff: int = 1024
    enc_layers: int = 6
    dec_layers: int = 6
    max_decode_len: int = 256
    max_src_len: int = 512


# ------------------------------------------------------------------ init --
def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def embed_init(key, vocab, dim, dtype=jnp.float32):
    return jax.random.normal(key, (vocab, dim), dtype) * (dim ** -0.5)


def dense_params(key, d_in, d_out):
    kw, _ = jax.random.split(key)
    return {"w": glorot(kw, (d_in, d_out)), "b": jnp.zeros((d_out,))}


def dense(p, x):
    return x @ p["w"] + p["b"]


# ----------------------------------------------------------------- cells --
def lstm_params(key, d_in, hidden):
    k1, k2 = jax.random.split(key)
    return {
        "wx": glorot(k1, (d_in, 4 * hidden)),
        "wh": glorot(k2, (hidden, 4 * hidden)),
        "b": jnp.zeros((4 * hidden,)),
    }


def lstm_cell(p, carry, x):
    """Standard LSTM cell; carry = (h, c)."""
    h, c = carry
    gates = x @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (h, c), h


def gru_params(key, d_in, hidden):
    k1, k2 = jax.random.split(key)
    return {
        "wx": glorot(k1, (d_in, 3 * hidden)),
        "wh": glorot(k2, (hidden, 3 * hidden)),
        "b": jnp.zeros((3 * hidden,)),
    }


def gru_cell(p, h, x):
    """Standard GRU cell; carry = h."""
    xz = x @ p["wx"] + p["b"]
    hz = h @ p["wh"]
    xr, xu, xn = jnp.split(xz, 3, axis=-1)
    hr, hu, hn = jnp.split(hz, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    u = jax.nn.sigmoid(xu + hu)
    n = jnp.tanh(xn + r * hn)
    h = (1.0 - u) * n + u * h
    return h, h


def scan_rnn(cell, params, init_carry, xs, reverse: bool = False):
    """Run a cell over the leading (time) axis of ``xs``."""
    def step(carry, x):
        return cell(params, carry, x)
    return jax.lax.scan(step, init_carry, xs, reverse=reverse)


def masked_scan_rnn(cell, params, init_carry, xs, mask,
                    reverse: bool = False):
    """Batched cell over the TIME axis of batch-major ``xs`` (B,N,...).

    ``mask`` (B,N) freezes the carry on padding steps (the ragged
    prefix-padded batches of the compiled decode path), so the final
    carry equals what the per-sequence unpadded scan would produce; pad
    positions emit zeros.  Returns ``(final_carry, outs (B,N,H))``.
    """
    xs_t = jnp.moveaxis(xs, 1, 0)
    m_t = jnp.moveaxis(mask, 1, 0)

    def step(carry, inp):
        x_t, m = inp
        new_carry, out = cell(params, carry, x_t)
        keep = m[:, None] > 0
        new_carry = jax.tree.map(
            lambda new, old: jnp.where(keep, new, old), new_carry, carry)
        return new_carry, jnp.where(keep, out, jnp.zeros_like(out))

    carry, outs = jax.lax.scan(step, init_carry, (xs_t, m_t),
                               reverse=reverse)
    return carry, jnp.moveaxis(outs, 0, 1)


# ------------------------------------------------------------- attention --
def luong_attention(query_h, enc_outs, enc_mask):
    """Dot-product (Luong) attention: (H,), (N,H), (N,) -> context (H,)."""
    scores = enc_outs @ query_h
    scores = jnp.where(enc_mask > 0, scores, -1e30)
    w = jax.nn.softmax(scores)
    return w @ enc_outs


def luong_attention_batch(query_h, enc_outs, enc_mask):
    """Batched Luong: (B,H), (B,N,H), (B,N) -> context (B,H)."""
    scores = jnp.einsum("bnh,bh->bn", enc_outs, query_h)
    scores = jnp.where(enc_mask > 0, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bn,bnh->bh", w, enc_outs)


# ----------------------------------------------------------------- decode --
def greedy_decode(decode_step, init_state, max_len: int,
                  forced_len: int | None = None):
    """Host-side greedy autoregressive loop.

    ``decode_step(state, token) -> (state, logits)`` must be jitted by the
    caller.  Returns (m_out, tokens).  The Python loop is intentional: its
    wall-clock is linear in the number of generated tokens M — the very
    property (paper §II-A, Fig. 2a) C-NMT's latency plane relies on.

    ``forced_len`` runs EXACTLY that many steps ignoring EOS — used by the
    offline characterization to sweep a controlled (N, M) grid with real
    model execution (an untrained model's natural stopping behaviour is
    degenerate; timing is what's being measured, not translation quality).
    """
    token = jnp.asarray(BOS_ID, jnp.int32)
    state = init_state
    out = []
    steps = forced_len if forced_len is not None else max_len
    for _ in range(steps):
        state, logits = decode_step(state, token)
        token = jnp.argmax(logits).astype(jnp.int32)
        tid = int(token)
        if forced_len is None and tid == EOS_ID:
            break
        out.append(tid)
    return len(out), jnp.asarray(out, jnp.int32)


def greedy_update(tok, done, *, keep_eos: bool = False,
                  forced: bool = False):
    """ONE emission step of the greedy EOS bookkeeping.

    ``tok`` (B,) is the carried token about to be emitted, ``done`` (B,)
    the rows already past their EOS.  Returns ``(emit, live, done2)``:
    the PAD-masked emission, the rows that emitted a real pre-EOS token
    this step (what ``lengths`` counts), and the updated done mask.

    This is the single source of truth for the EOS/done semantics —
    :func:`scan_greedy_steps` applies it inside its scan body and the
    continuous slot-table session
    (:class:`repro.runtime.serving.ContinuousGenerationSession`) applies
    it once per in-flight step, so block and continuous decode cannot
    drift apart.
    """
    if forced:
        return tok, jnp.ones(tok.shape, bool), done
    is_eos = tok == EOS_ID
    live = ~(done | is_eos)                  # emits a real token now
    emit = (jnp.where(done, PAD_ID, tok) if keep_eos
            else jnp.where(live, tok, PAD_ID))
    return emit, live, done | is_eos


def scan_greedy_steps(decode_step, state, token0, batch: int, steps: int, *,
                      keep_eos: bool = False, forced: bool = False):
    """The shared compiled greedy-decode scan body.

    Carry is ``(state, next_token (B,), done (B,))``; each of the
    ``steps`` iterations emits the carried token, then steps the model
    once to produce the next (``decode_step(state, tokens (B,)) ->
    (state, logits (B,V))``).  EOS bookkeeping stays on-device:

    * ``keep_eos=False`` PAD-masks the EOS slot itself (the NMT models'
      contract — emitted tokens are exactly the pre-EOS output);
    * ``keep_eos=True`` emits the EOS token and PAD-masks only the
      positions after it (the serving sessions' contract);
    * ``forced=True`` ignores EOS entirely (controlled-(N, M) grids).

    Returns ``(lengths (B,), tokens (B, steps))`` device arrays, lengths
    counting pre-EOS tokens either way.  Both
    :func:`batched_greedy_decode` and
    :class:`repro.runtime.serving.GenerationSession` build on this one
    body, so EOS/done semantics cannot drift between them.
    """
    done0 = jnp.zeros((batch,), bool)

    def step(carry, _):
        state, tok, done = carry
        emit, live, done2 = greedy_update(tok, done, keep_eos=keep_eos,
                                          forced=forced)
        state, logits = decode_step(state, tok)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (state, nxt, done2), (emit, live)

    _, (toks, live) = jax.lax.scan(step, (state, token0, done0),
                                   None, length=steps)
    lengths = jnp.sum(live.astype(jnp.int32), axis=0)
    return lengths, jnp.transpose(toks)          # (B,), (B, steps)


def batched_greedy_decode(decode_step, init_state, batch: int, max_len: int,
                          forced_len: int | None = None):
    """Compiled batched greedy decode: ONE ``lax.scan`` over decode steps.

    ``decode_step(state, tokens (B,)) -> (state, logits (B,V))`` must carry
    a leading batch dimension (the models' ``decode_step`` with batched
    state, or a ``jax.vmap`` of the per-sequence step).  EOS handling is
    on-device: a ``done`` mask freezes finished sequences (their emitted
    slots become PAD) while the scan keeps stepping the still-live ones —
    no per-token host round-trip.

    Returns ``(lengths (B,) int32, tokens (B, steps) int32)`` as device
    arrays: per-sequence output length EXCLUDING the EOS token (the
    paper's M, matching :func:`greedy_decode`'s ``m_out`` per sequence)
    and the emitted tokens, PAD-masked at and after each EOS.

    ``forced_len`` runs exactly that many steps ignoring EOS — same
    controlled-(N, M)-grid contract as :func:`greedy_decode`.
    """
    steps = forced_len if forced_len is not None else max_len
    state, logits = decode_step(init_state,
                                jnp.full((batch,), BOS_ID, jnp.int32))
    token0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return scan_greedy_steps(decode_step, state, token0, batch, steps,
                             keep_eos=False, forced=forced_len is not None)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EncoderStates:
    """The wire format of a split placement's encoder→decoder hand-off.

    ``data`` is the model-specific encoder output pytree (hidden state
    for the GRU, annotation vectors + carries for the BiLSTM, memory +
    mask for the transformer); ``src_lens`` (B,) int32 carries the true
    source lengths so the decode tier can rebuild ragged masks without
    re-reading the tokens.  Registered as a pytree so it passes through
    ``jax.jit`` boundaries and serializes leaf-by-leaf.
    """

    data: object
    src_lens: jnp.ndarray

    def tree_flatten(self):
        return (self.data, self.src_lens), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, src_lens = children
        return cls(data, src_lens)

    @property
    def batch(self) -> int:
        return int(self.src_lens.shape[0])

    def payload_bytes(self) -> int:
        """Actual wire size: sum of leaf nbytes (what a split executor
        reports to the engine, vs. the scheduler's a-priori
        ``ActivationCostModel`` estimate)."""
        leaves = jax.tree_util.tree_leaves((self.data, self.src_lens))
        return int(sum(np.asarray(leaf).size * np.asarray(leaf).dtype.itemsize
                       for leaf in leaves))


def build_encode_states(model, params, encode_data):
    """Shared scaffolding behind the models' ``make_encode_states``.

    ``encode_data(src (B,N), src_mask (B,N)) -> pytree`` is the
    model-specific encoder pass; the wrapper jits it and packs the
    result into :class:`EncoderStates` with the per-row source lengths.
    The jitted program is named ``nmt_encode_states``.
    """
    @jax.jit
    def nmt_encode_states(src, src_mask):
        data = encode_data(src, src_mask)
        lens = jnp.sum((src_mask > 0).astype(jnp.int32), axis=-1)
        return EncoderStates(data, lens)

    def encode_states(src, src_mask=None):
        src = jnp.asarray(src, jnp.int32)
        if src_mask is None:
            src_mask = jnp.ones(src.shape, jnp.float32)
        return nmt_encode_states(src, jnp.asarray(src_mask))

    return encode_states


def build_decode_from_states(model, params, state_from_data):
    """Shared scaffolding behind the models' ``make_decode_from_states``.

    ``state_from_data(data) -> batched decode state`` rebuilds the
    model's decode-step carry from the shipped :class:`EncoderStates`
    payload (identity for the RNNs; the transformer re-derives its
    cross-attention K/V cache decoder-side so only the raw memory
    crosses the wire).  The decode itself is the exact
    :func:`batched_greedy_decode` scan the fused path runs — parity with
    ``make_translate_batched`` is pinned bit-for-bit in tests.  The
    jitted program is named ``nmt_decode_states``.
    """
    step = lambda st, tok: model.decode_step(params, st, tok)

    @functools.partial(jax.jit, static_argnames=("forced_len",))
    def nmt_decode_states(states, forced_len=None):
        state = state_from_data(states.data)
        batch = states.src_lens.shape[0]
        return batched_greedy_decode(step, state, batch,
                                     model.cfg.max_decode_len, forced_len)

    def decode_from_states(states, forced_len=None):
        return nmt_decode_states(states, forced_len=forced_len)

    return decode_from_states


def build_translate_batched(model, params, make_state, *,
                            compiled: bool = True):
    """Shared scaffolding behind the models' ``make_translate_batched``.

    ``make_state(params, src (B,N), src_mask (B,N)) -> batched decode
    state`` is the only model-specific piece (encode + state assembly);
    stepping is ``model.decode_step`` with a leading batch dim.
    ``compiled=True`` jits encoder + state init + the whole scan decode
    into ONE dispatch per (B, N) shape, with the parameters as an
    argument (closed over, they would be baked into every executable as
    constants: slow to compile and a copy per shape); ``compiled=False``
    is the per-sequence host loop (the paper-faithful, linear-in-M
    timing path).  Both return ``translate(src, src_mask=None,
    forced_len=None) -> (lengths (B,), tokens (B, steps))``; the compiled
    one exposes its jitted ``(params, src, src_mask)`` step as
    ``translate.jitted``, named ``nmt_translate`` (``jit_nmt_translate``
    on a profiler trace's XLA Modules line).
    """
    if not compiled:
        translate = model.make_translate(params)

        def translate_host(src, src_mask=None, forced_len=None):
            return host_translate_batched(translate, src, src_mask,
                                          forced_len)
        return translate_host

    @functools.partial(jax.jit, static_argnames=("forced_len",))
    def nmt_translate(params, src, src_mask, forced_len=None):
        state = make_state(params, src, src_mask)
        step = lambda st, tok: model.decode_step(params, st, tok)
        return batched_greedy_decode(step, state, src.shape[0],
                                     model.cfg.max_decode_len, forced_len)

    def translate_batch(src, src_mask=None, forced_len=None):
        src = jnp.asarray(src, jnp.int32)
        if src_mask is None:
            src_mask = jnp.ones(src.shape, jnp.float32)
        return nmt_translate(params, src, jnp.asarray(src_mask),
                             forced_len=forced_len)

    translate_batch.jitted = nmt_translate
    return translate_batch


def host_translate_batched(translate, src_tokens, src_mask=None,
                           forced_len: int | None = None):
    """Paper-faithful batch fallback: per-sequence HOST-loop translate.

    Runs ``translate`` (a model's ``make_translate`` closure) row by row
    over a prefix-padded batch — one jitted dispatch per token per
    sequence, the timing-faithful slow path the compiled scan is measured
    against.  Returns ``(lengths (B,), tokens (B, width))`` numpy arrays,
    PAD-filled past each row's length, mirroring
    :func:`batched_greedy_decode`'s contract.
    """
    src = np.asarray(src_tokens, np.int32)
    b, n = src.shape
    mask = (np.ones((b, n), np.float32) if src_mask is None
            else np.asarray(src_mask))
    src_lens = mask.astype(bool).sum(axis=1)
    lengths = np.zeros((b,), np.int32)
    rows = []
    for i in range(b):
        m_out, toks = translate(src[i, :int(src_lens[i])],
                                forced_len=forced_len)
        lengths[i] = int(m_out)
        rows.append(np.asarray(toks, np.int32))
    width = max(1, max(len(r) for r in rows))
    out = np.full((b, width), PAD_ID, np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return lengths, out


def cross_entropy(logits, targets, mask):
    """Masked token-mean CE. logits (…,V), targets (…), mask (…)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
