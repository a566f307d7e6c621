"""Bytes the paged decode kernel must read, from shapes: memory-bound.

One call serves one layer and one token per row: it reads the K and V
pages that hold each row's context (whole pages: a page is the unit the
kernel fetches), reads q and writes o. FLOPs are 4 x heads x head_dim per
context token, far under the chip's ratio of 240 FLOP a byte, so bytes over
peak bandwidth is the bound.
"""

from __future__ import annotations

import math


def paged_decode_bytes(context_lens, kv_heads: int, heads: int,
                       head_dim: int, page_size: int,
                       itemsize: int = 2) -> float:
    """Bytes for ONE layer's call over rows with the given context lengths
    (0 for a parked row)."""
    pages = sum(math.ceil(n / page_size) for n in context_lens if n > 0)
    kv = 2.0 * pages * page_size * kv_heads * head_dim * itemsize
    rows = sum(1 for n in context_lens if n > 0)
    qo = 2.0 * rows * heads * head_dim * itemsize
    return kv + qo


def paged_decode_flops(context_lens, heads: int, head_dim: int) -> float:
    return 4.0 * heads * head_dim * float(sum(context_lens))
