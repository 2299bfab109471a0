"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself and
nothing here overrides it. Otherwise the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, gitignored), so every run of the
same checkout finds what earlier runs compiled — the directory is part of
the cache key, so it never carries a temp name, a PID or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
