"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call ``enable_compile_cache()`` from their ``main()``; nothing
calls it at import. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads
it itself, and no other directory is set here). Otherwise the cache lives
at ``<repo>/.jax_cache``: the directory is part of every entry's key, so a
temp-, pid- or time-derived path would never hit.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
