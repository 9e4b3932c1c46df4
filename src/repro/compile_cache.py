"""JAX's persistent compilation cache for the repo's entry points.

Called by ``chip_smoke.py`` and the benchmark entry points, never on
import: a library import must not redirect its caller's cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-repo location (listed in ``.gitignore``); the path is part
#: of the cache key, so it must not move between runs
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is set here; otherwise the cache goes to
    ``<repo>/.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
