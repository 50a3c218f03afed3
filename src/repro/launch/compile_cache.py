"""JAX's persistent compilation cache, placed from outside or at one
fixed path inside the checkout.

Entry points (``serve.py``, ``train.py``, ``chip_smoke.py``) call
:func:`enable_compile_cache` from their ``main()``; nothing calls it on
import or in tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it and no other directory is set here. Otherwise the cache lives
at ``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the
path is part of the cache key — a temporary or per-process name would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (this file is src/repro/launch/compile_cache.py)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
