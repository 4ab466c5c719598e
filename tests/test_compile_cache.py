"""Where the entry points put JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                            cache_dir_restored):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_in_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path   # stable across calls
