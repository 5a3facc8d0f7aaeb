"""The one place that picks the persistent compile-cache directories.

Every entry point calls :func:`use_persistent_cache` before its first
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which
reads it itself; otherwise the cache lives at a fixed ``.jax_cache/`` in
the checkout root. The path is part of what a cache entry is found by,
so it must not move between runs.

The sweep's own executable serialization (``sim/sweep.py``) is switched
on by ``REPRO_COMPILE_CACHE_DIR``; callers that want it without naming a
directory use :data:`SWEEP_DIR`.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")
SWEEP_DIR = os.path.join(DEFAULT_DIR, "sweep")


def use_persistent_cache() -> str:
    """Point JAX's persistent cache at ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set; returns the directory in use.
    Takes effect only before the process's first compilation."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
