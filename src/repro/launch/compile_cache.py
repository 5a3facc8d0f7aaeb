"""The one place that picks the persistent compile-cache directories.

Every entry point calls :func:`use_persistent_cache` before its first
compile. ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which
reads it itself; otherwise the cache lives at a fixed ``.jax_cache/`` in
the checkout root. The path is part of what a cache entry is found by,
so it must not move between runs.

:func:`metadata_in_key` keys the entries of the compiles inside it on
the program's metadata too (the FedFog round's, whose phase map lives in
its metadata).

The sweep's own executable serialization (``sim/sweep.py``) is switched
on by ``REPRO_COMPILE_CACHE_DIR``; callers that want it without naming a
directory use :data:`SWEEP_DIR`.
"""
from __future__ import annotations

import contextlib
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


@contextlib.contextmanager
def metadata_in_key():
    """Compiles inside this context key their persistent-cache entries on
    the program's metadata too (scopes, source files and lines). By
    default JAX strips it from the key, and an executable loaded from an
    entry that other metadata wrote carries that metadata: a phase map read
    from its text would be stale or empty. The cost: an entry is found
    again only while the traced source lines stay where they were."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)
