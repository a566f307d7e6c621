"""Operations of causal flash attention, forward and backward, from shapes.

Forward: QK^T and PV, 2 FLOP a multiply-add, half the square under the
causal mask. Backward as the kernels compute it: dq needs S = QK^T again,
dP = dO V^T and dQ = dS K (3 products); dkv needs S again, dP again, dV =
P^T dO and dK = dS^T Q (4 products). That recomputation is the algorithm's
own, so it counts: 2 + 3 + 4 = 9 products against the forward's 2. At 4096
and head 128 the kernel is compute-bound: bytes are q, k, v, o once.
"""

from __future__ import annotations


def _product(batch, seq, heads, head_dim, causal=True):
    return 2.0 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1)


def flash_flops(batch: int, seq: int, heads: int, head_dim: int,
                causal: bool = True) -> dict:
    p = _product(batch, seq, heads, head_dim, causal)
    return {"fwd": 2 * p, "dq": 3 * p, "dkv": 4 * p, "total": 9 * p}


def flash_bytes(batch, seq, heads, kv_heads, head_dim, itemsize=2) -> float:
    """q, o read/written once per pass, k, v once per pass (3 passes)."""
    qo = 2 * batch * seq * heads * head_dim * itemsize
    kv = 2 * batch * seq * kv_heads * head_dim * itemsize
    return 3.0 * (qo + kv)
