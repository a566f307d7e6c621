"""Where the persistent XLA compile cache lives.

Entry points that compile the big programs (``chip_smoke.py``, ``bench.py``,
the procfleet worker) call :func:`enable_compile_cache` before their first
use of a backend. The directory is part of the cache key, so it is either
the one the environment names or one fixed path beside the package — never
a temp name, a pid or a time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

#: ``<checkout>/.jax_cache`` — the directory that holds ``paddle_tpu/``
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point jax's persistent compile cache at a placeable directory and
    return it. ``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in
    code (jax reads the variable itself at import); unset, the cache is
    ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
