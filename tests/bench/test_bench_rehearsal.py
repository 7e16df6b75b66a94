"""CPU rehearsal of each cell's harness path at tiny size, and the faults
that must turn ``correct`` false.

Each run goes through ``bench/run.py``'s ``main`` as the chip run does,
minus the look for a TPU: a smoke configuration, a few requests, Pallas
in interpret mode.  The result line has the contract's keys, every
host-clock metric the cell lists comes back as a number, and no device
metric appears (a CPU run never reports one).

The fault cases break the timed path underneath a run and see the
check fail: a token altered where the served path produces it, and a
decode step that returns its state unchanged.  The control case puts the
lower-precision reference in the program's place (``--control 1``) and
sees the same.
"""

import gc
import os

import jax
import numpy as np
import pytest

from bench import harness, run

MARIAN = "marian-en-zh.sentences-poisson"
SMALL = {
    MARIAN: (
        {"scale": 0.0625, "d_model": 32, "encoder_attention_heads": 2,
         "decoder_attention_heads": 2, "encoder_ffn_dim": 128,
         "encoder_layers": 1, "decoder_layers": 1, "vocab_size": 300,
         "max_length": 12, "check": {"sample": 3, "limit": 1e-3,
                                     "min_tokens": 12,
                                     "control": "float8"}},
        {"prompt": {"median": 10, "sigma": 0.6, "min": 4, "max": 16,
                    "grid": None}, "max_new": 12}),
}
# at these sizes the sound runs read a gap of 0 on the CPU; each limit
# only has to sit below what a fault reads


@pytest.fixture
def rehearse():
    """Runs ``main`` on the CPU and restores the JAX settings it changes."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_default_matmul_precision")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")

    def go(cell, trace=0, seed=2 ** 33 + 1, control=0):
        cfg, mix = SMALL[cell]
        return run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "1", "--trace", str(trace),
                         "--control", str(control)],
                        require_tpu=False, cfg_update=cfg, mix_update=mix)
    yield go
    gc.unfreeze()                     # a run freezes what set-up made
    for k, v in saved.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env


def _host_metrics(cell, trace):
    bench = harness.load_json(run.ROOT / "BENCHMARK.json")
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return {m["name"] for m in group
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"
            and "mfu" not in m["name"]}


@pytest.mark.parametrize("cell", [MARIAN])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_line_and_metrics(rehearse, cell, trace):
    res = rehearse(cell, trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    want = _host_metrics(cell, trace)
    assert want <= set(res["metrics"]), want - set(res["metrics"])
    for name in set(res["metrics"]) - want:
        pytest.fail(f"device metric {name} printed from a CPU run")
    for m in res["metrics"].values():
        assert np.isfinite(m["value"])


def _alter_marian_token(monkeypatch):
    from repro.nmt.transformer import MarianTransformer
    orig = MarianTransformer.make_translate_batched

    def patched(self, params, **kw):
        translate = orig(self, params, **kw)

        def wrong(src, mask=None, forced_len=None):
            lens, toks = translate(src, mask, forced_len=forced_len)
            toks = np.array(toks)
            toks[:, 3] = (toks[:, 3] + 1) % self.cfg.vocab_tgt
            return lens, toks
        return wrong
    monkeypatch.setattr(MarianTransformer, "make_translate_batched", patched)


def _stale_marian_state(monkeypatch):
    from repro.nmt.transformer import MarianTransformer
    orig = MarianTransformer._decode_step_batch

    def stale(self, params, state, token):
        _, logits = orig(self, params, state, token)
        return {**state, "pos": state["pos"] + 1}, logits
    monkeypatch.setattr(MarianTransformer, "_decode_step_batch", stale)


@pytest.mark.parametrize("cell,fault", [
    (MARIAN, _alter_marian_token), (MARIAN, _stale_marian_state)])
def test_fault_under_the_timed_path_is_not_correct(rehearse, monkeypatch,
                                                   cell, fault):
    fault(monkeypatch)
    res = rehearse(cell)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", [MARIAN])
def test_control_in_the_programs_place_is_not_correct(rehearse, cell):
    """The control goes through the run's own check at the cell's limit;
    the program's own gap, reported beside it, stays within the limit."""
    res = rehearse(cell, control=1)
    assert res["correct"] is False
    checks = res["checks"]
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
    assert checks["program_logit_gap"]["value"] <= \
        checks["program_logit_gap"]["limit"]
