"""Persistent compile cache placement (repro.jitcache.enable_compile_cache)."""
from __future__ import annotations

import os

import jax

from repro import jitcache

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is the cache, and nothing is
    configured over it in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert jitcache.compile_cache_dir() == str(tmp_path)
    assert jitcache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_ignored_repo_dir(monkeypatch):
    """Unset, the cache is the fixed <repo>/.jax_cache, which git ignores."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jitcache.compile_cache_dir() == want
    was = jax.config.jax_compilation_cache_dir
    try:
        assert jitcache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().splitlines()
