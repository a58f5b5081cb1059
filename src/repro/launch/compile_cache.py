"""JAX's persistent compilation cache, in one place.

``use_compile_cache()`` is called at start-up by every entry point that
compiles for a device (``repro.launch.serve``, ``repro.launch.train``,
``chip_smoke.py``, ``benchmarks/run.py``). Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the path is part of what a later process must find again
(a temp name, a pid or a time would never hit).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
