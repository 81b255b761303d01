"""JAX's persistent compilation cache for the repo's entry points.

``python chip_smoke.py`` and the ``main``s of ``benchmarks/`` and
``examples/`` call ``enable_compile_cache()`` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed
path, because the directory is part of what a later run looks up, so a
name that changes per run would never hit.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``cache_dir()`` and return it."""
    if not os.environ.get(ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return cache_dir()
