"""The entry points' persistent compilation cache placement."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE, use_compile_cache


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory setting after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_variable_stands(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing


def test_fixed_checkout_directory_otherwise(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compile_cache()
    assert first == use_compile_cache() == str(CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == first
    repo = Path(__file__).resolve().parents[1]
    assert Path(first) == repo / ".jax_cache"
