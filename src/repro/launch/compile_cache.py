"""Persistent XLA compilation cache for the entry points.

``enable_compile_cache()`` is called once by each entry point
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py`` and
its workers) before anything compiles.  When ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here; otherwise the cache
lives at a fixed path inside the checkout, ``<repo>/.jax_cache``, so
every process of every run finds what an earlier one compiled.  The
path never depends on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
