"""Serving-side compute units.

``make_prefill_step`` / ``make_serve_step`` return exactly the functions
the multi-pod dry-run lowers for the prefill/decode input shapes — one
new token against a KV cache (or SSM state) of the configured context.

:class:`GenerationSession` drives them for real CPU generation (smoke
scale).  Decode has two paths:

* **compiled scan** (default): prefill once, then ONE ``jax.lax.scan``
  over all ``max_new`` decode steps with the EOS ``done`` mask kept
  on-device — a single XLA dispatch per generate call and a single
  device->host transfer at the end, instead of one dispatch + sync per
  token.  Post-EOS positions are PAD-masked and per-sequence output
  lengths are returned (:meth:`GenerationSession.generate_with_lengths`).
* **host loop** (``host_loop=True``): the per-token dispatch loop whose
  wall-clock is linear in the generated length M — the paper-faithful
  timing path (§II-A), kept for characterization runs.

Input shapes are padded to LENGTH BUCKETS (batch -> next power of two,
prompt width -> next bucket boundary) so each (batch, width, max_new)
triple compiles exactly once; a one-line warning is logged per new
compiled shape.  Width bucketing right-pads with PAD and threads true
per-sequence ``lengths`` through ``LM.prefill`` — numerically invisible
for position-masked mixers (attn/mla/shared_attn); plans with recurrent
mixers (mamba2/rwkv6) skip width bucketing since their carried state
would fold the pad steps in.

:func:`build_executor` is the ONE factory for every executor shape a
:class:`~repro.runtime.engine.Tier` accepts: ``kind="solo"`` adapts a
session into the per-request ``tokens -> (m_out, out_tokens)`` callable,
``kind="batched"`` into its REAL batched counterpart — one drained
:class:`~repro.data.pipeline.TokenBatcher` batch in, one batched
generate, per-sequence ``(m_out, tokens)`` out — which the engine's
``submit_batch`` uses so real execution matches the batch-aware
occupancy accounting; ``kind="split"`` returns the two legs of a split
placement; ``kind="raw"`` passes an existing executor through (for
fault-wrapping).  ``faults=...`` wraps the result with deterministic
fault injection.  The PR-era names (``make_tier_executor``,
``make_batched_tier_executor``, ``make_split_tier_executors``,
``make_faulty_executor``) remain as thin aliases that emit
``DeprecationWarning``.

:class:`ContinuousGenerationSession` (continuous in-flight batching) is
the Orca/vLLM-style refactor of the block path: a PERSISTENT slot table
of ``max_slots`` sequences decodes one step per dispatch, finished rows
are EVICTED between steps, queued prompts are PREFILLED INTO the freed
slots of the live batch (bucketed ragged ``prefill(lengths=...)``, rows
scattered into the resident decode state), and tokens stream out per
step instead of one end-of-block transfer.  A drained block no longer
runs to completion — one long sequence cannot hold ``max_slots - 1``
finished rows hostage, which is the p95 lever under heavy Poisson load
(ROADMAP item 1).  EOS/done semantics come from the same
:func:`~repro.nmt.common.greedy_update` the compiled scan uses, so the
two paths cannot drift; ``serve(..., refill=False)`` degenerates to
exact block-to-completion scheduling for the parity pins.

Everything built here plugs into :class:`~repro.runtime.engine.Tier`s
of the ``CollaborativeEngine``, which the load-generation harness
(``benchmarks/loadgen.py``) drives under MLPerf-style arrival
processes, recording completions through the engine's ``on_complete``
hook — see ``docs/architecture.md`` for the request lifecycle.
"""

from __future__ import annotations

import logging
import warnings
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.faults import TierFaultError
from repro.data.tokenizer import EOS_ID, PAD_ID
from repro.models.model import LM
from repro.nmt.common import greedy_update, scan_greedy_steps

_LOG = logging.getLogger(__name__)

# mixers whose decode caches are position-masked per sequence (slot ==
# position, mask idx <= pos), making right-padded ragged prefill exact
_POSITION_MASKED_MIXERS = ("attn", "mla", "shared_attn")


def _ragged_plan_ok(model: LM) -> bool:
    """True when ragged right-padded prompts are exact for this plan
    (every mixer's decode cache is position-masked per sequence)."""
    return all(g.mixer in _POSITION_MASKED_MIXERS
               for g in model.cfg.layer_plan)


def make_prefill_step(model: LM, *, max_len: Optional[int] = None) -> Callable:
    """prefill_step(params, tokens[, lengths][, frames]) ->
    (last_logits, decode_state)."""

    def prefill_step(params, tokens, lengths=None, frames=None):
        kw = {"frames": frames} if frames is not None else {}
        return model.prefill(params, tokens, max_len=max_len,
                             lengths=lengths, **kw)

    return prefill_step


def make_serve_step(model: LM) -> Callable:
    """serve_step(params, state, tokens (B,1)) -> (logits (B,V), state).

    ONE new token per sequence against the fixed-capacity decode state —
    the unit lowered for decode_32k / long_500k.
    """

    def serve_step(params, state, tokens):
        return model.decode_step(params, state, tokens)

    return serve_step


def _next_pow2(n: int, floor: int = 1) -> int:
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def _solo_executor(session: "GenerationSession", *, max_new: int = 16,
                   vocab_clip: Optional[int] = None) -> Callable:
    """Per-request ``executor(tokens) -> (m_out, out_tokens)``.

    ``vocab_clip`` guards against out-of-vocab ids when the request
    stream's tokenizer is larger than the serving model's.  ``m_out`` is
    the TRUE per-sequence output length (pre-EOS tokens) — finished
    sequences don't inflate M with post-EOS argmax junk.
    """

    def executor(tokens: np.ndarray):
        toks = np.asarray(tokens, np.int32)[None, :]
        if vocab_clip is not None:
            toks = np.minimum(toks, vocab_clip - 1)
        lens, out = session.generate_with_lengths(toks, max_new=max_new)
        m = int(lens[0])
        return m, out[0, :max(m, 1)]

    return executor


def _faulty_wrap(executor: Callable, should_fail,
                 *, message: str = "injected tier fault") -> Callable:
    """Wrap a REAL tier executor with deterministic fault injection.

    ``should_fail`` decides per call whether this invocation crashes:
    either a ``Callable[[int], bool]`` of the 0-based call index, or a
    collection of call indices.  A failing call raises
    :class:`TierFaultError` *instead of* executing — modelling a crash
    before useful work, which is what the engine's detection/retry
    arithmetic assumes.  The wrapper exposes ``.calls`` (``{"n": total,
    "faults": raised}``) so tests can assert the injection actually
    fired.  This is the REAL-execution twin of the modelled
    :class:`~repro.core.faults.FaultSchedule` injection: the schedule
    drives virtual-time faults inside the engine/DES, this wrapper
    drives them through the executor boundary the engine cannot see
    into.
    """
    if not callable(should_fail):
        wanted = frozenset(int(i) for i in should_fail)
        should_fail = wanted.__contains__
    calls = {"n": 0, "faults": 0}

    def faulty(tokens: np.ndarray):
        i = calls["n"]
        calls["n"] += 1
        if should_fail(i):
            calls["faults"] += 1
            raise TierFaultError(f"{message} (call {i})")
        return executor(tokens)

    faulty.calls = calls
    return faulty


def _block_and_lengths(batch, lengths, vocab_clip):
    """A drained (b, width) block and its true per-row prompt lengths
    (derived from trailing PADs when ``lengths`` is None)."""
    toks = np.asarray(batch, np.int32)
    if toks.ndim != 2:
        raise ValueError("batched executor expects a (b, width) block")
    if vocab_clip is not None:
        toks = np.minimum(toks, vocab_clip - 1)
    if lengths is not None:
        return toks, np.asarray(lengths, np.int32)
    real = toks != PAD_ID
    # width minus trailing pads; clamp to >= 1 for all-pad rows
    trailing = np.where(real.any(1), np.argmax(real[:, ::-1], axis=1),
                        toks.shape[1])
    return toks, np.maximum(toks.shape[1] - trailing, 1).astype(np.int32)


def _batched_executor(session: "GenerationSession", *,
                      max_new: int = 16,
                      vocab_clip: Optional[int] = None) -> Callable:
    """REAL batched ``executor(batch, lengths=None)``.

    Returns ``executor(batch, lengths=None) -> [(m_out, tokens), ...]``:
    ``batch`` is one drained :class:`TokenBatcher` padded token block
    (b, width) — already length-bucketed by the batcher — and ``lengths``
    the true per-request prompt lengths (derived from trailing PADs when
    omitted).  One batched ``generate`` serves the whole batch; results
    come back per sequence in row order, so the engine can account each
    member of the batch individually.
    """

    def executor(batch: np.ndarray, lengths: Optional[Sequence[int]] = None):
        toks, lens_in = _block_and_lengths(batch, lengths, vocab_clip)
        if session.supports_ragged or np.all(lens_in == toks.shape[1]):
            m_out, out = session.generate_with_lengths(
                toks, max_new=max_new, lengths=lens_in)
            return [(int(m), out[i, :max(int(m), 1)])
                    for i, m in enumerate(m_out)]
        # recurrent-state plans can't take ragged right-padding: run one
        # uniform (trimmed) sub-batch per distinct length instead
        results: List[Optional[tuple]] = [None] * toks.shape[0]
        for L in np.unique(lens_in):
            rows = np.flatnonzero(lens_in == L)
            m_out, out = session.generate_with_lengths(
                toks[rows, :int(L)], max_new=max_new)
            for j, r in enumerate(rows):
                results[r] = (int(m_out[j]), out[j, :max(int(m_out[j]), 1)])
        return results

    return executor


def _start_copies_to_host(*arrays) -> bool:
    """Start each output's copy to the host without waiting for it; True
    when every one is a device array whose copy was started (a NumPy
    output has none to start)."""
    starts = [getattr(a, "copy_to_host_async", None) for a in arrays]
    for start in starts:
        if start is not None:
            start()
    return all(start is not None for start in starts)


def _nmt_batched_executor(model, params, *,
                          vocab_clip: Optional[int] = None) -> Callable:
    """REAL batched executor for an NMT seq2seq model (the paper's tiers).

    Same contract as :func:`_batched_executor`, served by the model's
    compiled ``make_translate_batched``.  Blocks are padded to
    power-of-two (batch, width) buckets, so a tier compiles one translate
    per bucket; padding rows and columns are masked out and dropped.  The
    translate function is exposed as ``executor.translate``.

    A translate crosses the host-device boundary once each way.  The
    padded int32 block and its int32 row lengths stay on the host until
    the one jitted call uploads them, folded into one array; the program
    builds the prefix mask itself.  Right after the call returns, the
    copies of both outputs to the host are started, so they run as soon
    as the program ends and the read-back finds them there.

    Traced as an ``exec.translate`` span (attrs ``b``, ``w``: the padded
    bucket) holding ``exec.dispatch`` (the asynchronous enqueue; attr
    ``h2d``, the host arrays the jitted call uploads, as the translate
    states it, else None) and ``exec.wait`` (the device work and the
    read-back; attr ``prefetch``, true when the outputs' copies to the
    host were started at dispatch).
    """
    translate = model.make_translate_batched(params)
    h2d = getattr(translate, "h2d", None)

    def executor(batch: np.ndarray, lengths: Optional[Sequence[int]] = None):
        with tracing.span("exec.translate") as sp:
            toks, lens_in = _block_and_lengths(batch, lengths, vocab_clip)
            b, w = toks.shape
            bb, wb = _next_pow2(b), _next_pow2(w, floor=8)
            sp.set(b=bb, w=wb)
            src = np.full((bb, wb), PAD_ID, np.int32)
            src[:b, :w] = toks
            lens = np.zeros((bb,), np.int32)
            lens[:b] = lens_in
            with tracing.span("exec.dispatch", h2d=h2d):
                m_out, out = translate(src, lens)
                prefetch = _start_copies_to_host(m_out, out)
            with tracing.span("exec.wait", prefetch=prefetch):
                m_out = np.asarray(m_out, np.int32)
                out = np.asarray(out, np.int32)
            return [(int(m_out[i]), out[i, :max(int(m_out[i]), 1)])
                    for i in range(b)]

    executor.translate = translate
    return executor


def _split_executors(model, params, *,
                     vocab_clip: Optional[int] = None
                     ) -> Tuple[Callable, Callable]:
    """Adapt an NMT model into the two LEGS of a split placement.

    Returns ``(encode_executor, decode_executor)`` for
    :class:`~repro.runtime.engine.Tier`:

    * ``encode_executor(tokens) -> EncoderStates`` runs just the encoder
      (1-D int token array in, shippable pytree out);
    * ``decode_executor(states) -> (m_out, out_tokens)`` resumes from the
      shipped states and runs the compiled scan decode.

    ``decode_executor(encode_executor(t))`` is bit-for-bit the fused
    ``make_translate_batched`` path (pinned in tests) — splitting is a
    placement choice, never a quality change.  Give the encode tier the
    first and the decode tier the second; a tier serving both legs of
    different requests can carry both.
    """
    encode_states = model.make_encode_states(params)
    decode_from_states = model.make_decode_from_states(params)

    def encode_executor(tokens: np.ndarray):
        toks = np.asarray(tokens, np.int32)[None, :]
        if vocab_clip is not None:
            toks = np.minimum(toks, vocab_clip - 1)
        return encode_states(toks)

    def decode_executor(states):
        lens, out = decode_from_states(states)
        m = int(np.asarray(lens)[0])
        return m, np.asarray(out, np.int32)[0, :max(m, 1)]

    return encode_executor, decode_executor


def build_executor(session_or_model, *, kind: str = "solo",
                   max_new: int = 16,
                   vocab_clip: Optional[int] = None,
                   params=None,
                   faults=None,
                   fault_message: str = "injected tier fault"):
    """The ONE factory for every executor shape a Tier accepts.

    ``kind`` selects the adaptation:

    * ``"solo"`` — ``session_or_model`` is a generation session; returns
      the per-request ``executor(tokens) -> (m_out, out_tokens)``.
    * ``"batched"`` — same input; returns the REAL batched
      ``executor(batch, lengths=None) -> [(m_out, tokens), ...]`` the
      engine's ``submit_batch`` drives (``Tier.batched_executor``).  With
      ``params=``, ``session_or_model`` is an NMT *model* instead, served
      by its compiled batched translate.
    * ``"split"`` — ``session_or_model`` is an NMT *model* and
      ``params=`` its parameters; returns the ``(encode_executor,
      decode_executor)`` pair for a partitioned placement
      (``Tier.encode_executor`` / ``Tier.decode_executor``).
    * ``"raw"`` — ``session_or_model`` is already an executor callable;
      passed through untouched (useful purely to apply ``faults=``).

    ``faults`` wraps the result with deterministic fault injection (a
    ``Callable[[int], bool]`` of the call index, or a collection of call
    indices — see :class:`TierFaultError`); the wrapper exposes
    ``.calls``.  ``faults`` composes with every kind except ``"split"``
    (two legs — wrap each leg yourself via ``kind="raw"``).
    """
    if kind == "solo":
        executor = _solo_executor(session_or_model, max_new=max_new,
                                  vocab_clip=vocab_clip)
    elif kind == "batched" and params is not None:
        executor = _nmt_batched_executor(session_or_model, params,
                                         vocab_clip=vocab_clip)
    elif kind == "batched":
        executor = _batched_executor(session_or_model, max_new=max_new,
                                     vocab_clip=vocab_clip)
    elif kind == "split":
        if params is None:
            raise ValueError("kind='split' needs params=")
        if faults is not None:
            raise ValueError(
                "faults= does not compose with kind='split' (two legs); "
                "wrap each leg via build_executor(leg, kind='raw', "
                "faults=...)")
        return _split_executors(session_or_model, params,
                                vocab_clip=vocab_clip)
    elif kind == "raw":
        if not callable(session_or_model):
            raise ValueError("kind='raw' expects an executor callable")
        executor = session_or_model
    else:
        raise ValueError(
            f"kind must be 'solo'|'batched'|'split'|'raw', got {kind!r}")
    if faults is not None:
        executor = _faulty_wrap(executor, faults, message=fault_message)
    return executor


def _warn_deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"{old} is deprecated; use {new}",
        DeprecationWarning, stacklevel=3)


def make_tier_executor(session, *, max_new: int = 16,
                       vocab_clip: Optional[int] = None) -> Callable:
    """Deprecated alias for ``build_executor(session, kind='solo')``."""
    _warn_deprecated("make_tier_executor",
                     "build_executor(session, kind='solo')")
    return build_executor(session, kind="solo", max_new=max_new,
                          vocab_clip=vocab_clip)


def make_batched_tier_executor(session, *, max_new: int = 16,
                               vocab_clip: Optional[int] = None) -> Callable:
    """Deprecated alias for ``build_executor(session, kind='batched')``."""
    _warn_deprecated("make_batched_tier_executor",
                     "build_executor(session, kind='batched')")
    return build_executor(session, kind="batched", max_new=max_new,
                          vocab_clip=vocab_clip)


def make_split_tier_executors(model, params, *,
                              vocab_clip: Optional[int] = None
                              ) -> Tuple[Callable, Callable]:
    """Deprecated alias for ``build_executor(model, kind='split')``."""
    _warn_deprecated("make_split_tier_executors",
                     "build_executor(model, kind='split', params=...)")
    return build_executor(model, kind="split", params=params,
                          vocab_clip=vocab_clip)


def make_faulty_executor(executor: Callable, should_fail,
                         *, message: str = "injected tier fault") -> Callable:
    """Deprecated alias for ``build_executor(executor, kind='raw',
    faults=...)``."""
    _warn_deprecated("make_faulty_executor",
                     "build_executor(executor, kind='raw', faults=...)")
    return build_executor(executor, kind="raw", faults=should_fail,
                          fault_message=message)


class GenerationSession:
    """Greedy batched generation on CPU (reduced configs).

    ``host_loop=True`` selects the per-token dispatch loop (the
    paper-faithful, linear-in-M timing path); the default is the
    compiled-scan fast path.  ``bucket_shapes=False`` disables the
    length-bucket padding (every distinct input shape then compiles its
    own executable, the seed behaviour).
    """

    def __init__(self, model: LM, params, *, max_len: int = 64,
                 host_loop: bool = False, bucket_shapes: bool = True):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.host_loop = host_loop
        self.bucket_shapes = bucket_shapes
        self._prefill = jax.jit(make_prefill_step(model, max_len=max_len))
        self._step = jax.jit(make_serve_step(model))
        self._decode = jax.jit(self._decode_scan,
                               static_argnames=("max_new",))
        self._compiled_shapes: set = set()
        self._ragged_ok = _ragged_plan_ok(model)

    @property
    def supports_ragged(self) -> bool:
        """True when ragged right-padded prompts are exact for this plan
        (every mixer's decode cache is position-masked per sequence)."""
        return self._ragged_ok

    # ------------------------------------------------------- scan decode --
    def _decode_scan(self, params, state, tok0, max_new: int):
        """All ``max_new`` decode steps in one lax.scan (the shared
        :func:`~repro.nmt.common.scan_greedy_steps` body); done stays on
        device.  Emits the EOS token itself (``keep_eos``), PAD-masks
        everything after it, and counts pre-EOS tokens per sequence."""

        def step(st, tok):                        # LM contract adapter
            logits, st2 = self.model.decode_step(params, st, tok[:, None])
            return st2, logits

        return scan_greedy_steps(step, state, tok0[:, 0], tok0.shape[0],
                                 max_new, keep_eos=True)

    # ------------------------------------------------------------ public --
    def generate(self, tokens: np.ndarray, *, max_new: int = 16,
                 frames: Optional[np.ndarray] = None,
                 lengths: Optional[Sequence[int]] = None) -> np.ndarray:
        """tokens (B,S) int32 -> generated (B,<=max_new) int32.

        Emitted rows end with EOS where the model produced one; positions
        after it are PAD (they no longer carry post-EOS argmax junk).
        Trailing all-PAD columns are trimmed (width >= 1 kept).
        """
        lens, out = self.generate_with_lengths(
            tokens, max_new=max_new, frames=frames, lengths=lengths)
        # lens counts pre-EOS tokens; +1 keeps the emitted EOS visible
        width = int(min(max(int(lens.max()) + 1, 1), out.shape[1]))
        return out[:, :width]

    def generate_with_lengths(
            self, tokens: np.ndarray, *, max_new: int = 16,
            frames: Optional[np.ndarray] = None,
            lengths: Optional[Sequence[int]] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """tokens (B,S) -> (lengths (B,), tokens (B,max_new)).

        ``lengths`` out counts each sequence's PRE-EOS tokens (the
        paper's M); the token block is PAD-masked after each EOS.
        ``lengths`` in marks true prompt lengths in a right-padded batch
        (position-masked mixer plans only).
        """
        tokens = np.asarray(tokens, np.int32)
        b, s = tokens.shape
        if s + max_new > self.max_len:
            raise ValueError("exceeds session capacity")
        lens_in = (None if lengths is None
                   else np.asarray(lengths, np.int32))
        if lens_in is not None and not self._ragged_ok:
            if np.all(lens_in == s):
                lens_in = None           # uniform full-width: nothing ragged
            else:
                raise ValueError(
                    "ragged prompt lengths need position-masked mixers "
                    f"(plan has {[g.mixer for g in self.model.cfg.layer_plan]})")
        if self.bucket_shapes and frames is None:
            tokens, lens_in = self._bucket_pad(tokens, lens_in, max_new)

        args = (self.params, jnp.asarray(tokens))
        if frames is not None:
            logits, state = self._prefill(*args, None, jnp.asarray(frames))
        elif lens_in is not None:
            logits, state = self._prefill(*args, jnp.asarray(lens_in))
        else:
            logits, state = self._prefill(*args)
        tok0 = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]

        if self.host_loop:
            lens_out, out = self._host_decode(state, tok0, max_new)
        else:
            lens_out, out = self._decode(self.params, state, tok0,
                                         max_new=max_new)
        return (np.asarray(lens_out, np.int32)[:b],
                np.asarray(out, np.int32)[:b])

    # ------------------------------------------------------------ helpers --
    def _bucket_pad(self, tokens, lens_in, max_new):
        """Pad (b, s) up to the shape bucket; returns (tokens, lengths)."""
        b, s = tokens.shape
        bb = _next_pow2(b)
        if self._ragged_ok:
            sb = min(_next_pow2(s, floor=8), self.max_len - max_new)
            sb = max(sb, s)
            if lens_in is None:
                lens_in = np.full((b,), s, np.int32)
        else:
            sb = s                       # recurrent state: exact width only
        if (bb, sb) != (b, s):
            padded = np.full((bb, sb), PAD_ID, np.int32)
            padded[:b, :s] = tokens
            tokens = padded
            if lens_in is not None:
                lens_in = np.concatenate(
                    [lens_in, np.ones((bb - b,), np.int32)])
        key = (bb, sb, max_new)
        if key not in self._compiled_shapes:
            self._compiled_shapes.add(key)
            _LOG.warning("GenerationSession: compiling new shape "
                         "batch=%d width=%d max_new=%d", bb, sb, max_new)
        return tokens, lens_in

    def _host_decode(self, state, tok0, max_new: int):
        """Per-token dispatch loop (timing path).  ``done`` stays on
        device; the early-exit check syncs ONE scalar per step instead of
        transferring the token block."""
        tok = tok0
        done = jnp.zeros((tok0.shape[0],), bool)
        emitted = []
        lens = jnp.zeros((tok0.shape[0],), jnp.int32)
        for _ in range(max_new):
            t = tok[:, 0]
            emitted.append(jnp.where(done, PAD_ID, t))
            lens = lens + (~done & (t != EOS_ID)).astype(jnp.int32)
            done = done | (t == EOS_ID)
            if bool(done.all()):                  # one scalar sync per step
                break
            logits, state = self._step(self.params, state, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out = jnp.stack(emitted, axis=1)
        if out.shape[1] < max_new:                # match scan-path width
            out = jnp.pad(out, ((0, 0), (0, max_new - out.shape[1])),
                          constant_values=PAD_ID)
        return lens, out


class ContinuousGenerationSession:
    """Continuous in-flight batching over a persistent slot table.

    ``max_slots`` sequences share ONE resident decode state (capacity
    ``max_len`` per slot).  The serving loop is re-formed *between decode
    steps*:

    * :meth:`step` runs one jitted decode dispatch over the whole slot
      table, streams each live slot's emitted token back (per-step
      transfer of ``max_slots`` scalars, not an end-of-block barrier),
      and EVICTS rows that emitted EOS or exhausted their ``max_new``
      budget — their slots free immediately;
    * :meth:`admit` PREFILLS queued prompts into the freed slots of the
      live batch: one bucketed ragged ``LM.prefill(lengths=...)`` per
      admission wave, its rows scattered into the resident state (KV
      caches at batch axis 1, ``pos`` at axis 0) with padding rows
      dropped through out-of-bounds scatter indices.

    EOS/done bookkeeping is :func:`repro.nmt.common.greedy_update` with
    ``keep_eos=True`` — the exact semantics of the compiled-scan
    :class:`GenerationSession` path, so a sequence's emitted tokens and
    pre-EOS length are identical to what a solo ``generate_with_lengths``
    call produces (the parity tests pin this row-for-row).

    Plans with recurrent mixers (mamba2/rwkv6) are admitted in
    exact-width groups (their carried state would fold right-padding in);
    position-masked plans take the bucketed ragged path.  Prompt batches
    are padded to power-of-two (batch, width) buckets so admission waves
    compile a bounded set of shapes.
    """

    def __init__(self, model: LM, params, *, max_slots: int = 8,
                 max_len: int = 64, bucket_shapes: bool = True):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if model.cfg.is_encoder_decoder:
            raise ValueError("continuous batching needs a decoder-only LM")
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.bucket_shapes = bucket_shapes
        self._ragged_ok = _ragged_plan_ok(model)
        self._prefill = jax.jit(make_prefill_step(model, max_len=max_len))
        self._step = jax.jit(self._cont_step)
        self._write = jax.jit(self._write_rows)
        self._compiled_shapes: set = set()
        self.reset()

    def reset(self) -> None:
        """Empty the slot table, KEEPING the compiled shapes — benchmarks
        warm a session once and reset between measured runs."""
        # resident device state: seeded by a dummy prefill so every leaf
        # has exactly the shape later admission prefills produce
        _, state = self._prefill(
            self.params, jnp.full((self.max_slots, 1), PAD_ID, jnp.int32))
        self._state = state
        self._tok = jnp.full((self.max_slots,), PAD_ID, jnp.int32)
        self._done = jnp.ones((self.max_slots,), bool)

        # host-side slot table
        self._live = np.zeros(self.max_slots, bool)
        self._req = [None] * self.max_slots     # caller's request id
        self._emitted: List[List[int]] = [[] for _ in range(self.max_slots)]
        self._m = np.zeros(self.max_slots, np.int64)     # pre-EOS count
        self._steps_left = np.zeros(self.max_slots, np.int64)
        self.n_steps = 0
        self.n_prefills = 0
        self.peak_live = 0

    # ---------------------------------------------------------- queries --
    @property
    def supports_ragged(self) -> bool:
        return self._ragged_ok

    @property
    def live_count(self) -> int:
        return int(self._live.sum())

    @property
    def free_slots(self) -> int:
        return self.max_slots - self.live_count

    # ------------------------------------------------------ jitted bodies --
    def _cont_step(self, params, state, tok, done):
        """One in-flight decode step over the whole slot table."""
        emit, live, done2 = greedy_update(tok, done, keep_eos=True)
        logits, state2 = self.model.decode_step(params, state, tok[:, None])
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return state2, nxt, emit, live, done2

    def _write_rows(self, state, new_state, slots, tok, done, tok0):
        """Scatter freshly prefilled rows into the resident state.

        ``slots`` may carry out-of-bounds indices (== max_slots) for the
        batch-bucket padding rows — JAX scatter drops those updates, so
        only the real admissions land."""
        caches = jax.tree.map(lambda a, b: a.at[:, slots].set(b),
                              state["caches"], new_state["caches"])
        out = {k: (caches if k == "caches"
                   else state[k].at[slots].set(new_state[k]))
               for k in state}
        return (out, tok.at[slots].set(tok0),
                done.at[slots].set(False))

    # ------------------------------------------------------------- admit --
    def admit(self, prompts: Sequence[np.ndarray], *, max_new: int = 16,
              req_ids: Optional[Sequence] = None) -> List[int]:
        """Prefill ``prompts`` into free slots of the LIVE batch.

        Returns the assigned slot indices (one per prompt, in order).
        Raises when more prompts than free slots are offered — the
        caller's admission control owns queueing, the slot table never
        oversubscribes.
        """
        if not prompts:
            return []
        free = np.flatnonzero(~self._live)
        if len(prompts) > len(free):
            raise ValueError(
                f"admit({len(prompts)}) exceeds {len(free)} free slots")
        toks = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        for t in toks:
            if len(t) + max_new > self.max_len:
                raise ValueError("exceeds session capacity")
            if len(t) == 0:
                raise ValueError("empty prompt")
        if req_ids is None:
            req_ids = list(range(len(prompts)))
        slots = [int(free[j]) for j in range(len(prompts))]

        if self._ragged_ok:
            groups = [list(range(len(toks)))]
        else:                     # recurrent state: exact width per group
            by_len: dict = {}
            for j, t in enumerate(toks):
                by_len.setdefault(len(t), []).append(j)
            groups = [by_len[L] for L in sorted(by_len)]
        for idx in groups:
            self._admit_group([toks[j] for j in idx],
                              [slots[j] for j in idx], max_new)

        for j, s in enumerate(slots):
            self._live[s] = True
            self._req[s] = req_ids[j]
            self._emitted[s] = []
            self._m[s] = 0
            self._steps_left[s] = max_new
        self.peak_live = max(self.peak_live, self.live_count)
        return slots

    def _admit_group(self, toks: List[np.ndarray], slots: List[int],
                     max_new: int) -> None:
        """One prefill wave: pad to the (batch, width) bucket, prefill,
        scatter the rows into the resident slot-table state."""
        k = len(toks)
        w = max(len(t) for t in toks)
        lens = np.asarray([len(t) for t in toks], np.int32)
        uniform = bool(np.all(lens == w))
        if self.bucket_shapes:
            kp = _next_pow2(k)
            if self._ragged_ok:
                wp = min(_next_pow2(w, floor=8), self.max_len - max_new)
                wp = max(wp, w)
            else:
                wp = w
        else:
            kp, wp = k, w
        block = np.full((kp, wp), PAD_ID, np.int32)
        for j, t in enumerate(toks):
            block[j, :len(t)] = t
        lens_in = np.concatenate([lens, np.ones(kp - k, np.int32)])
        key = (kp, wp, "prefill")
        if key not in self._compiled_shapes:
            self._compiled_shapes.add(key)
            _LOG.warning("ContinuousGenerationSession: compiling admission "
                         "shape batch=%d width=%d", kp, wp)
        if self._ragged_ok and not (uniform and kp == k and wp == w):
            logits, new_state = self._prefill(
                self.params, jnp.asarray(block), jnp.asarray(lens_in))
        else:
            logits, new_state = self._prefill(self.params,
                                              jnp.asarray(block))
        tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
        # bucket-padding rows scatter to index max_slots: out of bounds,
        # dropped — only the k real rows land in the table
        slot_idx = np.full(kp, self.max_slots, np.int32)
        slot_idx[:k] = slots
        self._state, self._tok, self._done = self._write(
            self._state, new_state, jnp.asarray(slot_idx),
            self._tok, self._done, tok0)
        self.n_prefills += 1

    # -------------------------------------------------------------- step --
    def step(self) -> Tuple[List[tuple], List[tuple]]:
        """One in-flight decode step for every live slot.

        Returns ``(stream, finished)``: ``stream`` is the per-step token
        stream ``[(req_id, token), ...]`` (EOS included when emitted) and
        ``finished`` lists the rows evicted this step as ``(req_id,
        m_out, tokens)`` — ``m_out`` counting pre-EOS tokens and
        ``tokens`` the emitted array (EOS kept, never PAD-padded).  Free
        slots are skipped; an empty table is a no-op.
        """
        if not self._live.any():
            return [], []
        state2, nxt, emit, live, done2 = self._step(
            self.params, self._state, self._tok, self._done)
        self._state, self._tok, self._done = state2, nxt, done2
        emit = np.asarray(emit)
        live_arr = np.asarray(live)
        done_h = np.asarray(done2)
        self.n_steps += 1

        stream: List[tuple] = []
        finished: List[tuple] = []
        exhausted = np.zeros(self.max_slots, bool)
        for s in np.flatnonzero(self._live):
            # every live slot entered the step with done=False (EOS and
            # budget rows evict immediately), so emit is a genuine token
            # — possibly a real token whose id equals PAD_ID
            t = int(emit[s])
            self._emitted[s].append(t)
            stream.append((self._req[s], t))
            self._m[s] += int(live_arr[s])
            self._steps_left[s] -= 1
            if done_h[s] or self._steps_left[s] <= 0:
                if not done_h[s]:      # budget out: silence the row too
                    exhausted[s] = True
                self._live[s] = False
                finished.append((self._req[s], int(self._m[s]),
                                 np.asarray(self._emitted[s], np.int32)))
                self._req[s] = None
                self._emitted[s] = []
        if exhausted.any():
            self._done = jnp.logical_or(self._done, jnp.asarray(exhausted))
        return stream, finished

    # ------------------------------------------------------------- serve --
    def serve(self, prompts: Sequence[np.ndarray], *, max_new: int = 16,
              refill: bool = True) -> List[Tuple[int, np.ndarray]]:
        """Scheduling-free driver: run ``prompts`` through the slot table.

        ``refill=True`` is continuous mode — freed slots are refilled
        from the queue between steps.  ``refill=False`` is the PR 3
        block-to-completion discipline: a block of up to ``max_slots``
        prompts is admitted only when the table is EMPTY and runs until
        every member finishes (the parity baseline).  Returns
        ``(m_out, tokens)`` per prompt, in prompt order.
        """
        results: List[Optional[Tuple[int, np.ndarray]]] = [None] * len(prompts)
        queue = list(range(len(prompts)))
        head = 0
        while head < len(queue) or self.live_count:
            can_admit = self.free_slots if (refill or self.live_count == 0) \
                else 0
            take = min(can_admit, len(queue) - head)
            if take:
                idx = queue[head:head + take]
                head += take
                self.admit([prompts[i] for i in idx], max_new=max_new,
                           req_ids=idx)
            _, finished = self.step()
            for rid, m, toks in finished:
                results[rid] = (m, toks)
        return results  # type: ignore[return-value]
