"""JAX's persistent compile cache is kept at one path between runs:
``JAX_COMPILATION_CACHE_DIR`` when set (never overridden), else a fixed
``.jax_cache/`` at the checkout root."""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_dir_is_honoured(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
