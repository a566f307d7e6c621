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

__all__ = ["enable_compile_cache", "first_call"]

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


def first_call(fn, /, *args, **kwargs):
    """``fn(*args, **kwargs)`` for a call that traces and lowers a program:
    run it above half a MiB of headroom on Python's frame stack.

    CPython 3.11+ keeps interpreter frames in 16 KiB chunks and unmaps a
    chunk as soon as the frame at its base returns. jax's tracing and
    lowering recurse some 150 frames deep with hot loops at every depth;
    wherever such a loop's callee is the first frame of a chunk, every
    iteration maps and unmaps one (mmap, munmap, page faults). Which loops
    are hit depends only on how many frames, and of what size, lie below the
    call, so an unrelated edit to a caller moved the warm set-up of the
    24-layer serving programs by 30% on the v5e's host (PERF.md section 6,
    PR 24). This frame asks for more than any chunk holds, so CPython gives
    it a chunk of its own of 1 MiB, and the frames above it find room there
    until it returns."""
    return fn(*args, **kwargs)


first_call.__code__ = first_call.__code__.replace(co_stacksize=(1 << 16) + 64)
