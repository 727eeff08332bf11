"""JAX's persistent compilation cache, placed once per entry point.

Every entry point's ``main`` calls :func:`enable_compile_cache` before it
compiles anything; importing a module never does. The directory is fixed,
so that a second run finds what the first wrote: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads that variable itself and nothing else is set
here), otherwise ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
