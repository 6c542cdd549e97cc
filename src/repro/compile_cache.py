"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` first thing in ``main``,
before anything compiles (JAX decides once per process whether to use the
cache, at its first compile).  Campaigns compile one lane-driver program per
lane bucket per app, and the server its prefill and decode programs; with
the cache, a second process on the same machine loads them instead.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<repo root>/.jax_cache`` (git-ignored), found from this file's location
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing is set in code.  Otherwise the cache lives in
    :data:`DEFAULT_CACHE_DIR`, a fixed path, since the path is part of what
    makes an entry found again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
