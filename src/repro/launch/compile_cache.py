"""Persistent XLA compile cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory and JAX
reads it itself; nothing here overrides it.  Otherwise the cache lives at a
fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
path is part of what a later run must find again, so it is never built
from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The in-checkout cache directory used when the environment names none.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; call before the first compile.

    Returns the directory the cache is written to.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
