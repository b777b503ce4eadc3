"""JAX's persistent compilation cache for the entry points.

Called by ``repro.launch.serve``, ``repro.launch.train`` and
``chip_smoke.py`` at start-up — never at library import. A set
``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is left alone;
otherwise the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored). The path is part of a cache entry's
key, so it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    if os.environ.get(ENV):
        return os.environ[ENV]       # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
