"""Where JAX keeps its persistent compilation cache.

Entry points (the serve and train CLIs, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`use_compile_cache` once before they compile;
``import repro`` never does.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
JAX reads it itself and nothing is set here.  Otherwise the cache lives at a
fixed path inside the checkout (``<checkout>/.jax_cache``, listed in
``.gitignore``): the cache key includes the directory, so a path that moved
from run to run would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
