"""Where JAX keeps its persistent compilation cache.

The Flare store (``FLARE_CACHE_DIR``) persists prepared executables;
JAX's own compilation cache sits below it and saves the XLA compile of
every program, Pallas kernels included.  :func:`enable_jax_compile_cache`
is called by the entry points that run on a chip (``chip_smoke.py``,
the benchmarks), never when the library is imported, so an embedding
application keeps control of JAX's configuration.

The directory is part of the cache's key space: a path that moves
between runs (a temp, pid or time-based directory) never hits.  So it
is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise one
fixed directory in the checkout, ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<repo>/.jax_cache``: this file is ``<repo>/src/repro/persist/``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def jax_compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_jax_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on at
    :func:`jax_compile_cache_dir` and return that directory.  Every
    program is cached, however fast it compiled: a query's programs
    each compile in well under JAX's default one-second threshold."""
    import jax
    path = jax_compile_cache_dir()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
