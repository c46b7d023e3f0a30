"""Placement of JAX's persistent compilation cache.

The cache key includes the directory, so a cache only hits when every run
points at the same one: ``$JAX_COMPILATION_CACHE_DIR`` where the machine
sets it (JAX reads that variable itself), else a fixed directory inside
the checkout — never a temporary, per-process or per-run path.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fallback: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Path:
    """The directory the compile cache lives in (no side effects)."""
    env = os.environ.get(CACHE_ENV)
    return Path(env) if env else CHECKOUT_CACHE_DIR


def enable_compile_cache() -> Path:
    """Turn the persistent compile cache on; call before the first compile.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and this
    sets nothing; otherwise it points JAX at :data:`CHECKOUT_CACHE_DIR`.
    Returns the directory in use.
    """
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
