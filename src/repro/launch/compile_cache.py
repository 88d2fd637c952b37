"""Persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Where it is unset, executables are cached under
``<repo>/.jax_cache``: a fixed path (never a temporary name, a pid or a
time), so every later process of this checkout finds what an earlier one
compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d
