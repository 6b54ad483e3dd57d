"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compilation_cache` once, before their first
compile.  ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the
cache lives in ``.jax_cache/`` at the root of this checkout.  The path never
depends on a temp name, a PID or the time: it is part of what makes a later
run find the entries an earlier one wrote.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compilation_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
