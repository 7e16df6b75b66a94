"""The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to one fixed, git-ignored path inside the checkout."""

from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # no override


def test_default_dir_is_fixed_and_git_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path          # same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert Path(path) == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
