"""Bytes the paged decode kernel must read on a layer with a window, from
shapes: memory-bound like ``ops/paged_decode.py``, whose count a layer
without a window keeps.

A call serves one layer and one token per row. With a window ``W`` the query
of a row whose context is ``n`` (its own position included) sees positions
``n - W .. n - 1``: the kernel starts its walk at the page that holds
``n - W`` and fetches whole pages from there to the page of ``n - 1``; the
pages behind are not read. q is read and o written as ever.
"""

from __future__ import annotations

import math


def window_pages(n: int, window: int, page_size: int) -> int:
    """Pages a row of context ``n`` makes a windowed layer's call read."""
    if n <= 0:
        return 0
    return math.ceil(n / page_size) - max(0, n - window) // page_size


def paged_decode_window_bytes(context_lens, window: int, kv_heads: int,
                              heads: int, head_dim: int, page_size: int,
                              itemsize: int = 2) -> float:
    """Bytes for ONE windowed layer's call over rows with the given context
    lengths (0 for a parked row)."""
    pages = sum(window_pages(n, window, page_size) for n in context_lens)
    kv = 2.0 * pages * page_size * kv_heads * head_dim * itemsize
    rows = sum(1 for n in context_lens if n > 0)
    qo = 2.0 * rows * heads * head_dim * itemsize
    return kv + qo
