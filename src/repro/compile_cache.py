"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile.  Importing the
library never turns the cache on, so ahead-of-time compiles for a
described (absent) TPU topology, as the tests make, never write to it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache;
otherwise a fixed ``<checkout>/.jax_cache``.  The path is part of the
cache key, so it must not be temporary or per-process: a directory that
moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(checkout: Path) -> Path:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else Path(checkout) / ".jax_cache"


def enable_compile_cache(checkout: Path) -> Path:
    """Turn the persistent cache on at :func:`compile_cache_dir` and return
    the directory.  Every program is cached, however fast it compiled: the
    engines compile one small fold/readout program per tenant block shape,
    and a cold run pays for all of them."""
    path = compile_cache_dir(checkout)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
