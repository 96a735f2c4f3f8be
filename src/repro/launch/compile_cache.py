"""The persistent XLA compilation cache, for the entry points.

A cold start compiles every full-width step again (tens of seconds per
program on a chip).  ``enable_compile_cache`` is called by the launchers,
the benchmarks and ``chip_smoke.py`` — never on import — so that a second
run of the same programs loads them instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout: a fixed path, because the directory is part of
    what a later run has to find again."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
