"""The persistent compile cache is placed from outside, or at one fixed
path in the checkout — never at a name built per run."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_fixed_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
