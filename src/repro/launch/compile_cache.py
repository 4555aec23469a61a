"""Persistent XLA compilation cache for the program's entry points.

JAX keys a cached executable on, among other things, the cache
directory, so the directory must not move between runs: a fixed path
inside the checkout (``.jax_cache/``, git-ignored) unless the
environment already names one.  Called from ``main`` functions only,
never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing else is configured here.  Otherwise the cache goes to
    ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
