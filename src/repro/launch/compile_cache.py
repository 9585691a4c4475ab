"""JAX's persistent compilation cache, switched on by the entry points.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``); importing
this module changes nothing. A cold chip run compiles every program, so a
second run that finds them cached starts in seconds instead of minutes.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# The repository checkout this package runs from (src/repro/launch/..).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing here changes. Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, never a temporary or per-process one, because a cache
    that moves between runs never hits.
    """
    env = os.environ.get(ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
