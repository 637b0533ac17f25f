"""Where the persistent compilation cache lives (``repro.launch.compile_cache``)."""

import os

import jax

from repro.launch.compile_cache import checkout_cache_dir, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_environment_directory_wins_and_nothing_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_directory_otherwise(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache") == checkout_cache_dir()
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path  # the same on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
