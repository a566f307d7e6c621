"""Bytes and FLOPs of one grouped (per-expert) matmul, from shapes.

``rows`` (token, expert) pairs, sorted by expert, are multiplied by the
matrix ``[d_in, d_out]`` of the expert each belongs to. What has to move: the
matrices of the experts that got a row or more (``experts_visited``: one
that got none need not be read), the rows in and the rows out; a kernel that
gives every expert whole tiles of ``tile`` rows moves and multiplies the
padding too. In the decode regime (a few rows an expert) the weights are all
but the whole of it, far under the chip's 240 FLOP a byte: bytes over peak
bandwidth is the bound. An expert FFN (SwiGLU) is three such matmuls: two
``hidden -> width`` and one ``width -> hidden``.
"""

from __future__ import annotations

import math


def padded_rows(rows: float, experts_visited: float, tile: int = 1) -> float:
    """Rows computed when every visited expert's rows fill whole tiles: at
    least one tile each, at most ``tile - 1`` rows of padding each."""
    if tile <= 1:
        return float(rows)
    least = experts_visited * tile
    return float(max(least, math.ceil(rows / tile) * tile))


def grouped_matmul_bytes(rows: float, experts_visited: float, d_in: int,
                         d_out: int, tile: int = 1,
                         itemsize: int = 2) -> float:
    p = padded_rows(rows, experts_visited, tile)
    return itemsize * (experts_visited * d_in * d_out + p * (d_in + d_out))


def grouped_matmul_flops(rows: float, experts_visited: float, d_in: int,
                         d_out: int, tile: int = 1) -> float:
    return 2.0 * padded_rows(rows, experts_visited, tile) * d_in * d_out


def expert_ffn_bytes(rows: float, experts_visited: float, hidden: int,
                     width: int, tile: int = 1, itemsize: int = 2) -> float:
    """The three grouped matmuls of one routed SwiGLU layer."""
    return (2 * grouped_matmul_bytes(rows, experts_visited, hidden, width,
                                     tile, itemsize)
            + grouped_matmul_bytes(rows, experts_visited, width, hidden,
                                   tile, itemsize))


def expert_ffn_flops(rows: float, experts_visited: float, hidden: int,
                     width: int, tile: int = 1) -> float:
    return 3 * grouped_matmul_flops(rows, experts_visited, hidden, width,
                                    tile)
