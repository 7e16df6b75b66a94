"""Random weights from a seed, made on the device in one jitted call.

The benchmark, not the program, draws every weight: the program's
``init`` gives only the shapes and dtypes of its parameter tree
(``jax.eval_shape``), and each leaf is drawn here from the seed and the
leaf's path.  So the plain references under ``bench/refs`` can draw the
very same values again, layer by layer, without taking anything the
program made.

A leaf's values depend only on (seed, path, layer): the stacked leaves of
a scanned layer group are drawn layer by layer under ``vmap``, and one
layer drawn alone (``LayerDrawer``) is bitwise the same slice.  Values
are drawn in float32 and cast to the leaf's dtype; a reference casts them
to that dtype and back, so it computes in float32 on the served values.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

Spec = List[Tuple[str, Tuple[int, ...], str]]   # (path, shape, dtype name)


def _key_str(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def path_str(path) -> str:
    return "/".join(_key_str(k) for k in path)


def spec_of(shape_tree) -> Spec:
    """(path, shape, dtype) of every leaf of an abstract parameter tree."""
    leaves = jax.tree_util.tree_flatten_with_path(shape_tree)[0]
    return [(path_str(p), tuple(l.shape), jnp.dtype(l.dtype).name)
            for p, l in leaves]


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of up to 64 bits as two uint32 words (a jit argument, so a
    new seed compiles nothing)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)


def _root_key(words):
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, words[0])
    return jax.random.fold_in(key, words[1])


def draw(key, path: str, shape: Sequence[int]) -> jnp.ndarray:
    """float32 values of one (unstacked) leaf, by the rule its name picks."""
    parts = path.split("/")
    name = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""
    shape = tuple(shape)
    normal = lambda: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    if parent == "mix":                     # token-shift interpolation
        return jax.random.uniform(key, shape, jnp.float32)
    if name == "w0":                        # per-channel base log decay
        return jax.random.uniform(key, shape, jnp.float32, -6.0, 0.0)
    if name == "u":                         # rwkv bonus
        return 0.5 * normal()
    if name in ("g", "ln_g"):               # norm gains
        return 1.0 + 0.1 * normal()
    if name in ("b", "ln_b"):               # biases and norm shifts
        return 0.1 * normal()
    if len(shape) >= 2:
        fan = shape[-1] if "embed" in path else shape[-2]
        return normal() * fan ** -0.5
    return normal()


def _leaf_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()))


def _make_leaf(root, path, shape, dtype, stacked: bool):
    key = _leaf_key(root, path)
    if not stacked:
        return draw(key, path, shape).astype(dtype)
    layers = jnp.arange(shape[0], dtype=jnp.uint32)
    one = lambda l: draw(jax.random.fold_in(key, l), path,  # noqa: E731
                         shape[1:]).astype(dtype)
    return jax.vmap(one)(layers)


def is_stacked(path: str, prefixes: Sequence[str]) -> bool:
    return any(path.startswith(p) for p in prefixes)


def make_params(shape_tree, seed: int, stacked: Sequence[str] = ()):
    """The whole parameter tree, on the default device, in one call."""
    spec = spec_of(shape_tree)
    treedef = jax.tree_util.tree_structure(shape_tree)

    def build(words):
        root = _root_key(words)
        return [_make_leaf(root, p, s, jnp.dtype(d), is_stacked(p, stacked))
                for p, s, d in spec]

    leaves = jax.jit(build)(seed_words(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


class LayerDrawer:
    """float32 values, as served, of the leaves under ``prefix``, one
    layer of a stacked group per call (unstacked leaves ignore the layer
    index), keyed by the path below ``prefix``.  One jit serves every
    layer, so a reference that walks the layers compiles once."""

    def __init__(self, spec: Spec, prefix: str, stacked: Sequence[str] = ()):
        chosen = [(p, s, d) for p, s, d in spec if p.startswith(prefix)]
        if not chosen:
            raise KeyError(f"no parameter under {prefix!r}")

        def build(words, layer):
            root = _root_key(words)
            out = {}
            for p, s, d in chosen:
                key = _leaf_key(root, p)
                if is_stacked(p, stacked):
                    key, s = jax.random.fold_in(key, layer), s[1:]
                out[p[len(prefix):]] = draw(key, p, s).astype(
                    jnp.dtype(d)).astype(jnp.float32)
            return out

        self._build = jax.jit(build)

    def __call__(self, seed: int, layer: int = 0) -> Dict[str, jnp.ndarray]:
        return self._build(seed_words(seed), jnp.uint32(layer))
