"""Process-wide shared jit registry (the ``_SHARED_JITS`` discipline).

Jit wrappers built in FUNCTION scope are a retrace hazard: every call of
the enclosing function makes a fresh closure, every fresh closure is a new
cache key to jax, and the same jaxpr gets re-traced (and on a cold XLA
cache, re-compiled) over and over. The engine learned this in PR 4 —
sharing its decode/prefill/assign jits across instances cut the serving
suites ~35% — and ``repro.analysis``'s jit-discipline pass now enforces it
everywhere: a ``jax.jit`` site must be module-level (built once per
import), routed through :func:`shared_jit` here, or carry an explicit
``# nbl: disable=jit-discipline -- <reason>`` allowlist comment.

Use it when the jitted closure captures only HASHABLE, value-equal
constants (a frozen ``ModelConfig``, static plan ints/bools): two builds
over equal keys lower to identical jaxprs, so handing every caller the
same callable lets jax's trace cache do its job. Do NOT use it when the
closure captures arrays (params) or mesh-captured shardings — those must
stay per-instance, and their sites carry allowlist reasons instead.

Keys are plain hashable tuples, conventionally ``("<site>", cfg, ...)``
with every closure-captured constant included — a key that under-describes
its closure silently serves the wrong function.

:func:`enable_compile_cache` is the other half: JAX's PERSISTENT
compilation cache, so separate processes (a server restart, the next
benchmark run) reuse compiled programs from disk. Entry points call it;
importing this module never does.
"""
from __future__ import annotations

import os
from typing import Callable

SHARED_JITS: dict = {}

# the fixed in-checkout default: the directory is part of the cache key, so
# a path that moved between runs (a temp name, a pid) would never hit
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", ".jax_cache")


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return os.path.normpath(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                            or DEFAULT_COMPILE_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, so
    nothing is configured here; otherwise the cache goes to the fixed
    in-checkout default."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def shared_jit(key, build: Callable):
    """Return the process-wide jit for ``key``, building it on first use."""
    fn = SHARED_JITS.get(key)
    if fn is None:
        fn = SHARED_JITS[key] = build()
    return fn
