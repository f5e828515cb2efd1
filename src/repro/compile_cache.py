"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed by its directory among other things, so the
directory must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR``
when that is set, and otherwise one fixed path inside the checkout,
``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
