"""Bit-transition reduction by data transmission ordering: the NoC
simulator, its packetizer and sweep engine, and the models that feed it.

Importing the package has no side effects. Entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/noc_inference.py``) call
:func:`enable_compile_cache` once, before their first compile.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "CACHE_DIR"]

# Fixed, repo-relative: the cache key includes the directory, so a path
# built from a temp name, a pid or the time would never hit.
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                         ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` already in the environment wins (JAX
    reads it itself) and nothing else is set. Otherwise the cache goes to
    ``<repo>/.jax_cache``, which ``.gitignore`` lists.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
