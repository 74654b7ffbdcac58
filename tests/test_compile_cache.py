"""Where the persistent compilation cache is placed."""
import os

import jax

from repro.launch import cache


def test_environment_cache_dir_is_left_to_jax(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert cache.configure_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_and_ignored(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = cache.configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(path)
    assert path == cache.CHECKOUT_CACHE_DIR
    assert os.path.exists(os.path.join(root, "pyproject.toml"))
    with open(os.path.join(root, ".gitignore")) as f:
        assert os.path.basename(path) + "/" in f.read().split()
