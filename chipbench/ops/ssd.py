"""Bytes and FLOPs of Mamba-2's state-space layer, from shapes: what the
algorithm needs, whatever implements it (the XLA form of
``paddle_tpu/ops/ssd.py`` today, a kernel later: the same yardstick).

``heads`` heads of ``head_dim`` channels over a state of ``state`` a head,
``groups`` groups of B and C, a convolution of ``taps`` taps over
``heads * head_dim + 2 * groups * state`` channels.

The one-token step (decode) of one row and one layer reads the matrix state
``[heads, head_dim, state]`` float32 and writes it back, reads the conv
window (``taps - 1`` inputs) and writes it back, reads the token's x, B, C,
dt and writes y: a few FLOPs a byte, far under the chip's 240, so bytes over
peak bandwidth is the bound.

The chunked scan (prefill) of one chunk of ``chunk`` positions and one layer
multiplies, a head: C B^T ``[chunk, chunk]`` over ``state`` (a group), the
masked product with x (``chunk x chunk x head_dim``), the chunk's own state
(``chunk x head_dim x state``) and the read of the incoming state (the same);
it reads x, B, C, dt, writes y, and reads and writes the state once. The
larger of FLOPs over peak and bytes over bandwidth is the least time.
"""

from __future__ import annotations


def conv_width(heads: int, head_dim: int, groups: int, state: int) -> int:
    return heads * head_dim + 2 * groups * state


def step_bytes(heads: int, head_dim: int, groups: int, state: int,
               taps: int = 4, itemsize: int = 2,
               state_itemsize: int = 4) -> float:
    """One row, one layer, one token step."""
    matrix = 2.0 * heads * head_dim * state * state_itemsize
    width = conv_width(heads, head_dim, groups, state)
    window = 2.0 * (taps - 1) * width * itemsize
    token = (width + heads + heads * head_dim) * itemsize      # xBC, dt, y
    return matrix + window + token


def step_flops(heads: int, head_dim: int, state: int) -> float:
    """Decay and update (3 a state element) and the read-out (2)."""
    return 5.0 * heads * head_dim * state


def chunk_flops(heads: int, head_dim: int, groups: int, state: int,
                chunk: int = 128) -> float:
    """One chunk, one layer: the four matmuls of the chunked form."""
    cb = 2.0 * groups * chunk * chunk * state
    diag = 2.0 * heads * chunk * chunk * head_dim
    local = 2.0 * heads * chunk * head_dim * state
    incoming = 2.0 * heads * chunk * head_dim * state
    return cb + diag + local + incoming


def chunk_bytes(heads: int, head_dim: int, groups: int, state: int,
                chunk: int = 128, itemsize: int = 2,
                state_itemsize: int = 4) -> float:
    """One chunk, one layer: x, B, C, dt in, y out, the state in and out."""
    width = conv_width(heads, head_dim, groups, state)
    tokens = chunk * (width + heads + heads * head_dim) * itemsize
    return tokens + 2.0 * heads * head_dim * state * state_itemsize
