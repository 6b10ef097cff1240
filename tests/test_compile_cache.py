"""Where ``repro.compile_cache.use_compile_cache`` puts JAX's persistent
compilation cache: the directory ``JAX_COMPILATION_CACHE_DIR`` names, left
to JAX, or else one fixed directory inside the checkout."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: the directory is part of the cache key
    assert compile_cache.use_compile_cache() == want
