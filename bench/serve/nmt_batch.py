"""Driver for an NMT configuration served per request through
``CollaborativeEngine.submit_batch`` (the paper's C-NMT path).

Two tiers share one chip and one set of parameters: a local ``edge`` and
a ``cloud`` behind the modelled link.  Both run the same compiled batched
translate, so only the link and the queues separate them.  Each arrival
is one ``submit_batch`` call at its virtual time; the engine's occupancy
carries over between calls, so a busy tier's queue enters the placement.
The window keeps feeding the seeded schedule until the host clock passes
``seconds``; every request it sends completes inside its own call.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import traffic, weights
from bench.harness import Spans
from bench.link import Link


class Driver:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, spans: Spans):
        import jax
        import jax.numpy as jnp
        from repro.models.registry import resolve

        self.cfg, self.mix, self.seed, self.spans = cfg, mix, seed, spans
        r = resolve(cfg["registry"], scale=cfg.get("scale", 1.0),
                    vocab=cfg["vocab_size"], max_decode_len=cfg["max_length"],
                    attn_impl=cfg["attn_impl"])
        mc = r.model.cfg
        got = {"d_model": mc.d_model, "encoder_attention_heads": mc.heads,
               "encoder_ffn_dim": mc.d_ff, "encoder_layers": mc.enc_layers,
               "decoder_layers": mc.dec_layers, "vocab_size": mc.vocab_tgt}
        for k, v in got.items():
            if cfg[k] != v:
                raise ValueError(f"model built with {k}={v}, config says "
                                 f"{cfg[k]}")
        self.model = r.model
        self.vocab = mc.vocab_tgt
        # the configuration's parameter dtype for every float leaf; the
        # model computes in float32 (its positions and caches are float32)
        dtype = jnp.dtype(cfg["param_dtype"])
        shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, dtype if jnp.issubdtype(l.dtype, jnp.floating)
                else l.dtype),
            jax.eval_shape(r.model.init, jax.random.PRNGKey(0)))
        self.spec = weights.spec_of(shapes)
        self.params = weights.make_params(shapes, seed)
        jax.block_until_ready(self.params)
        self.link = Link(cfg["link"])
        self.calls: List[Dict] = []        # one per translate in the window
        self.served: Dict[int, np.ndarray] = {}
        self.recording = False

    # -------------------------------------------------------------- tiers --
    def _executor(self, tier: str, translate):
        def run(block, lengths=None):
            with self.spans.span("translate"):
                t0 = time.perf_counter()
                outs = translate(block, lengths)
                wall = time.perf_counter() - t0
            if self.recording:
                self.calls.append({"tier": tier, "wall": wall, "t0": t0,
                                   "shape": np.shape(block),
                                   "lengths": list(lengths),
                                   "m_out": [int(m) for m, _ in outs]})
                self._last = [np.asarray(t) for _, t in outs]
            return outs
        return run

    def _engine(self):
        from repro.core.latency_model import DeviceProfile, LinearLatencyModel
        from repro.core.length_regressor import LinearN2M
        from repro.runtime.engine import CollaborativeEngine, Tier

        tiers = []
        for name, t in self.cfg["tiers"].items():
            prof = DeviceProfile(name, LinearLatencyModel(**t["plane"]))
            remote = t.get("link", False)
            tiers.append(Tier(
                prof, name=name, batched_executor=self._executor(
                    name, self.translate),
                rtt_fn=self.link.rtt_at if remote else None,
                bandwidth_bps=self.link.bandwidth_bps))
        eng = CollaborativeEngine(tiers=tiers, n2m=LinearN2M(
            **self.cfg["n2m"]), seed=self.seed % 2 ** 32,
            refit_interval=self.cfg.get("refit_interval"))
        sched = eng.scheduler
        decide = sched.decide

        def timed_decide(*a, **k):
            with self.spans.span("decide"):
                return decide(*a, **k)
        sched.decide = timed_decide
        return eng

    # ------------------------------------------------------------- set-up --
    def setup(self) -> None:
        """Compile and run every shape the window uses: one translate per
        width bucket at batch 1, and the engine's decision path."""
        from repro.runtime.serving import build_executor

        self.translate = build_executor(self.model, kind="batched",
                                        params=self.params)
        p = self.mix["prompt"]
        widths = sorted({max(8, 1 << (n - 1).bit_length())
                         for n in range(p["min"], p["max"] + 1)})
        for w in widths:
            self.translate(np.full((1, w), traffic.FIRST_ID, np.int32), [w])
        warm_engine = self._engine()
        for i, w in enumerate(widths):
            warm_engine.submit_batch(
                [np.full(w, traffic.FIRST_ID, np.int32)], now_s=float(i))
        self.engine = self._engine()

    # ------------------------------------------------------------- window --
    def window(self, seconds: float, mark=None) -> Dict:
        """Serve the schedule for ``seconds`` of host time; ``mark`` is
        called with the elapsed seconds before each request."""
        eng = self.engine
        self.recording = True
        self.calls, self.served = [], {}
        self.prompts: List[np.ndarray] = []
        results = []
        t0 = time.perf_counter()
        end = t0 + seconds
        ep = 0
        done = False
        while not done:
            arr, prompts = traffic.episode(self.mix, self.seed, ep, self.vocab)
            ep += 1
            for t, prompt in zip(arr, prompts):
                now = time.perf_counter()
                if now >= end:
                    done = True
                    break
                if mark is not None:
                    mark(now - t0)
                self._last = None
                rid = len(self.prompts)
                self.prompts.append(prompt)
                with self.spans.span("engine"):
                    res = eng.submit_batch([prompt], now_s=float(t))[0]
                results.append(res)
                if not res.shed and self._last is not None and res.m_out:
                    self.served[rid] = self._last[0][:res.m_out]
        wall = time.perf_counter() - t0
        self.recording = False
        self.results = results
        return {"wall_s": wall, "episodes": ep}

    # ------------------------------------------------------------ records --
    def records(self) -> Dict:
        res = self.results
        ok = [r for r in res if not r.shed]
        return {
            "attempted": len(res),
            "failed": len(res) - len(ok),
            "latency_s": [r.latency_s for r in ok],
            "wait_s": [r.wait_s for r in ok],
            "tokens_out": int(sum(r.m_out for r in ok)),
            "tier_counts": {n: sum(r.tier_name == n for r in ok)
                            for n in self.cfg["tiers"]},
            "translate_s": [c["wall"] for c in self.calls],
            "nm": [(len(self.prompts[i]), len(t))
                   for i, t in self.served.items()],
        }

    # the flash-decode kernel's custom-call name in the device trace
    kernel_patterns = {"flash_decode": "flash_decode"}

    def kernel_calls(self, kernel: str, between=None):
        """Cost arguments of every call of ``kernel`` made by the window's
        translates that began in ``between`` (host clock; all if None).

        A translate of an n-token source runs 1 + max_length decode steps
        (the BOS step, then the scan), each with one self-attention call
        over pos + 1 cached positions and one cross-attention call over n,
        per decoder layer.  Rows of one translate are summed into one
        call: the cost is linear in them."""
        if kernel != "flash_decode":
            return []
        c = self.cfg
        heads, dh = c["decoder_attention_heads"], c["d_model"] // c[
            "decoder_attention_heads"]
        steps = 1 + c["max_length"]
        out = []
        lo, hi = between or (-np.inf, np.inf)
        for call in self.calls:
            if not lo <= call["t0"] < hi:
                continue
            for n in call["lengths"]:
                lens = list(range(1, steps + 1)) + [n] * steps
                out.append((heads, heads, dh, lens * c["decoder_layers"]))
        return out

    def free(self) -> None:
        self.engine = self.translate = self.params = None

    def check_sample(self, k: int, seed: int):
        """(prompt, served tokens) of k completed requests drawn from the
        seed, the longest among them."""
        from bench.harness import sample_indices
        ids = sorted(self.served)
        size = [len(self.prompts[i]) + len(self.served[i]) for i in ids]
        return [(self.prompts[ids[j]], self.served[ids[j]])
                for j in sample_indices(size, k, seed)]
