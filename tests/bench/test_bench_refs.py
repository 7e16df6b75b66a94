"""The benchmark's plain references against the served paths, on the CPU
at small size.

What is compared is what a benchmark run compares: the tokens the served
path emitted, scored by the reference's logits at the same positions
(``harness.logit_gaps``: how far each served token's reference logit
lies below the reference's best).  Both sides run float32 here, so a
served token can differ from the reference's choice only at a near-tie
that float32 rounding decides: the tolerance 1e-4 is that rounding on
logits of order 1 (differences in summation order across a few layers),
and a wrong mask, cache or state puts gaps at order 0.1 and above.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights

ROOT = Path(__file__).resolve().parents[2]
TOL = 1e-4


def _ref_module(name):
    path = ROOT / "bench" / "refs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _prompts(rng, lengths, vocab):
    return [rng.integers(3, vocab, size=n).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def marian_served():
    from repro.models.registry import resolve
    from repro.runtime.serving import build_executor

    r = resolve("cnmt:en-zh", scale=0.0625, vocab=300, max_decode_len=24,
                attn_impl="pallas")
    shapes = jax.eval_shape(r.model.init, jax.random.PRNGKey(0))
    seed = 2 ** 33 + 7
    params = weights.make_params(shapes, seed)
    ex = build_executor(r.model, kind="batched", params=params)
    prompts = _prompts(np.random.default_rng(0), [5, 9, 3], 300)
    block = np.zeros((3, 9), np.int32)
    for i, p in enumerate(prompts):
        block[i, :len(p)] = p
    outs = ex(block, [len(p) for p in prompts])
    served = [np.asarray(t)[:m] for m, t in outs]
    cfg = {"d_model": r.cfg.d_model, "encoder_attention_heads": r.cfg.heads,
           "encoder_layers": r.cfg.enc_layers,
           "decoder_layers": r.cfg.dec_layers}
    ref = _ref_module("marian-en-zh").Reference(cfg, weights.spec_of(shapes),
                                                seed)
    assert all(len(s) == 24 for s in served)
    return ref, prompts, served, "float8"


def test_reference_matches_served_path(marian_served):
    """Marian through ``make_translate_batched``, float32 weights as
    configured."""
    ref, prompts, toks, _ = marian_served
    logits = ref.logits(prompts, toks)
    gaps = [harness.logit_gaps(l, s).max() for l, s in zip(logits, toks)]
    assert max(gaps) <= TOL, gaps


def test_lower_precision_control_fails(marian_served):
    """The control: the reference computed in float8, one step below the
    bfloat16 arithmetic of float32 matmuls at the default precision, read
    at the same positions, must miss the tolerance the served path
    meets."""
    ref, prompts, toks, low = marian_served
    hi = ref.logits(prompts, toks)
    lo = ref.logits(prompts, toks, precision=low)
    gap = max(harness.control_gaps(h, l).max() for h, l in zip(hi, lo))
    assert gap > 10 * TOL, gap


@pytest.mark.parametrize("stacked", [True, False])
def test_one_layer_drawn_alone_is_the_served_slice(stacked):
    from repro.configs import smoke_config
    from repro.models.model import LM

    model = LM(smoke_config("rwkv6-3b"), param_dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = "groups/0/" if stacked else "embed/"
    params = weights.make_params(shapes, 99, stacked=["groups/"])
    drawn = weights.LayerDrawer(weights.spec_of(shapes), prefix,
                                ["groups/"])(99, 1)
    flat = {weights.path_str(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for k, v in drawn.items():
        served = flat[prefix + k]
        served = served[1] if stacked else served
        np.testing.assert_array_equal(np.asarray(served, np.float32),
                                      np.asarray(v))
