"""JAX's persistent compilation cache, placed from outside.

Entry points (scripts, benchmarks, examples) call ``enable()`` once at
start-up; importing the library never touches the cache. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is changed here; otherwise the cache goes to one fixed directory in
the checkout. The path is part of what a cache entry is found under,
so it never comes from a temporary name, a process id or the time.

This is XLA's executable cache, keyed by the lowered program. The
query service's compiled-plan cache (``QueryService(persist_dir=...)``,
core/persist.py) is a separate, higher layer.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
