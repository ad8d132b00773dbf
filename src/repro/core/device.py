"""The backend JAX runs on, and the persistent compilation cache.

Kernel dispatch asks :func:`on_tpu` which implementation to run.  It lets a
backend error propagate: a TPU runtime that fails to start must stop the
program, not make it carry on silently on the CPU.

:func:`enable_compile_cache` is called by entry scripts (``chip_smoke.py``,
``benchmarks/``), never at import, so tests and library users keep JAX's
defaults.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))  # <checkout>/src/repro/core


def on_tpu() -> bool:
    """True iff JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def enable_compile_cache(root: str = CHECKOUT) -> str:
    """Keep compiled programs in JAX's persistent cache; returns its path.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself).
    Otherwise the cache lives at ``<root>/.jax_cache``: a fixed path, so the
    next run in the same checkout finds what this one compiled.
    Every compile is kept, however short: the DP's power-of-two batch
    buckets each compile in seconds and a cold start pays all of them.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
