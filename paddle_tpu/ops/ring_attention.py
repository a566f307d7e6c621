"""Ring attention — context-parallel causal attention over the ``sep`` mesh axis.

The reference's long-context story is SP/SEP activation sharding + flash-attention
kernels only — it has NO ring attention (SURVEY.md §5.7, grep-verified). This
exceeds it: Q stays local, K/V blocks rotate around the ring via
``lax.ppermute`` over ICI while each step's partial attention is merged through
logsumexp stats, so attention over sequence length n_dev × local_len never
materializes on one chip.

Two sequence layouts:
  - ``contiguous``: rank r holds global chunk r. Simple, but causal
    block-skipping makes rank i compute i+1 blocks — the ring runs at the
    speed of the LAST rank (n× the first's work).
  - ``zigzag`` (default): the sequence is cut into 2n stripes; rank r holds
    stripes (r, 2n-1-r). Every rank then computes exactly 2n+1 stripe-pairs
    of causal work — balanced. The global<->zigzag permutation is applied
    inside the global view (GSPMD lowers it to collectives).

The inner stripe-pair attention runs the in-repo Pallas flash kernel on TPU
(GQA folded into its BlockSpec index maps — K/V never repeated) and returns
logsumexp for the cross-step merge; CPU/odd shapes use an einsum fallback that
also avoids materializing repeated K/V heads.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# stripe-pair attention with lse output (merge-ready)
# ---------------------------------------------------------------------------

def _block_attn_lse(q, k, v, causal: bool, scale: float):
    """q [b,sq,h,d], k/v [b,sk,hkv,d] -> (out fp32 [b,sq,h,d], lse fp32
    [b,sq,h]). GQA is computed batched over kv-heads — no jnp.repeat."""
    if (jax.default_backend() == "tpu"
            and q.shape[1] == k.shape[1]
            and q.shape[1] % 8 == 0 and q.shape[-1] in (64, 128, 256)):
        from .flash_attention import (_tuned_block, _use_pallas,
                                      flash_attention_with_lse)

        bq = min(_tuned_block(q.shape[1]), q.shape[1])
        bk = min(_tuned_block(k.shape[1]), k.shape[1])
        if _use_pallas(q, k, bq, bk, False):
            # custom_vjp entry — differentiable through BOTH outputs (the
            # merge needs d/dlse; a bare pallas_call has no transpose rule)
            out, lse = flash_attention_with_lse(q, k, v, causal, scale,
                                                bq, bk, False)
            # lse: [b, h, sq] -> [b, sq, h]
            return out.astype(jnp.float32), jnp.swapaxes(lse, 1, 2)
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32)) * scale
    if causal:
        tri = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(tri[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    l_safe = jnp.where(l > 0, l, 1.0)
    out = out / l_safe[..., None]
    lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
    # [b,hkv,g,q,d] -> [b,q,h,d]; [b,hkv,g,q] -> [b,q,h]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, sq, hq, d)
    lse = jnp.transpose(lse, (0, 3, 1, 2)).reshape(b, sq, hq)
    return out, lse


def _merge(acc, lse, out_j, lse_j):
    """Merge two normalized partial attentions via their logsumexps."""
    new = jnp.logaddexp(lse, lse_j)
    w1 = jnp.exp(lse - new)[..., None]
    w2 = jnp.exp(lse_j - new)[..., None]
    return acc * w1 + out_j * w2, new


# ---------------------------------------------------------------------------
# zigzag layout helpers
# ---------------------------------------------------------------------------

def zigzag_perm(s_global: int, n: int) -> np.ndarray:
    """Index array P with x_zigzag = x[:, P]: rank r's contiguous shard holds
    global stripes (r, 2n-1-r)."""
    c = s_global // (2 * n)
    order = []
    for r in range(n):
        order += [r, 2 * n - 1 - r]
    return np.concatenate([np.arange(ch * c, (ch + 1) * c) for ch in order])


def zigzag_inverse(s_global: int, n: int) -> np.ndarray:
    perm = zigzag_perm(s_global, n)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(s_global)
    return inv


def _zigzag_pair_counts(n: int):
    """Per-rank stripe-pair compute counts (test hook: must be all equal).

    Rank r at ring step j (kv from rank s=(r-j)%n) computes:
      qA(r)      vs kA(s):      iff r >= s
      qB(2n-1-r) vs kA(s):      always
      qB(2n-1-r) vs kB(2n-1-s): iff s >= r
    """
    counts = []
    for r in range(n):
        c = 0
        for j in range(n):
            s = (r - j) % n
            c += (r >= s) + 1 + (s >= r)
        counts.append(c)
    return counts


# ---------------------------------------------------------------------------
# ring bodies
# ---------------------------------------------------------------------------

def _ring_body_zigzag(q, k, v, axis_name: str, scale: float, n: int):
    """Causal ring over zigzag-laid-out shards. Local seq = [stripe A; stripe
    B] with A = global stripe r, B = global stripe 2n-1-r. Balanced: every
    rank computes 2n+1 stripe-pairs total."""
    r = jax.lax.axis_index(axis_name)
    b, sl, h, d = q.shape
    c = sl // 2
    perm = [(i, (i + 1) % n) for i in range(n)]

    qA, qB = q[:, :c], q[:, c:]
    accA = jnp.zeros((b, c, h, d), jnp.float32)
    lseA = jnp.full((b, c, h), NEG_INF, jnp.float32)
    accB = jnp.zeros_like(accA)
    lseB = jnp.full_like(lseA, NEG_INF)

    kc, vc = k, v
    for j in range(n):  # n is small and static — unrolled, differentiable
        s = (r - j) % n
        kA, kB = kc[:, :c], kc[:, c:]
        vA, vB = vc[:, :c], vc[:, c:]

        # qB vs kA: B (stripe 2n-1-r) is always in kA's causal future — full
        outBA, lseBA = _block_attn_lse(qB, kA, vA, False, scale)
        accB, lseB = _merge(accB, lseB, outBA, lseBA)

        if j == 0:
            # own K/V (s == r, statically): both diagonals are triangular
            outd, lsed = _block_attn_lse(qA, kA, vA, True, scale)
            accA, lseA = _merge(accA, lseA, outd, lsed)
            outd2, lsed2 = _block_attn_lse(qB, kB, vB, True, scale)
            accB, lseB = _merge(accB, lseB, outd2, lsed2)
        else:
            # s != r here, so EXACTLY ONE of (qA vs kA | qB vs kB) is causal:
            # r > s -> qA attends kA fully; s > r -> qB attends kB fully.
            # One lax.cond computes just that block — per-step work is equal
            # on every rank (the balance claim; see _zigzag_pair_counts).
            def qa_branch(_):
                return _block_attn_lse(qA, kA, vA, False, scale)

            def qb_branch(_):
                return _block_attn_lse(qB, kB, vB, False, scale)

            out_x, lse_x = jax.lax.cond(r > s, qa_branch, qb_branch, None)
            mA = _merge(accA, lseA, out_x, lse_x)
            mB = _merge(accB, lseB, out_x, lse_x)
            pred = r > s
            accA = jnp.where(pred, mA[0], accA)
            lseA = jnp.where(pred, mA[1], lseA)
            accB = jnp.where(pred, accB, mB[0])
            lseB = jnp.where(pred, lseB, mB[1])

        if j + 1 < n:
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)

    return jnp.concatenate([accA, accB], axis=1).astype(q.dtype)


def _ring_body_contiguous(q, k, v, axis_name: str, causal: bool, scale: float,
                          n: int):
    """Plain ring: rank r holds global chunk r (r+1 causal blocks of work)."""
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    # step 0 is ALWAYS the own-block diagonal (src == idx statically):
    # peel it so the loop body computes only full (unmasked) blocks — no
    # double tri+full evaluation per step
    acc0, lse0 = _block_attn_lse(q, k, v, causal, scale)
    kc0 = jax.lax.ppermute(k, axis_name, perm)
    vc0 = jax.lax.ppermute(v, axis_name, perm)

    def body(j, carry):
        acc, lse, kc, vc = carry
        src = (idx - j) % n

        def compute(args):
            acc, lse, kc, vc = args
            out_j, lse_j = _block_attn_lse(q, kc, vc, False, scale)
            return _merge(acc, lse, out_j, lse_j)

        def skip(args):
            acc, lse, _, _ = args
            return acc, lse

        if causal:
            acc, lse = jax.lax.cond(src > idx, skip, compute, (acc, lse, kc, vc))
        else:
            acc, lse = compute((acc, lse, kc, vc))
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return acc, lse, kc, vc

    acc, lse, _, _ = jax.lax.fori_loop(1, n, body, (acc0, lse0, kc0, vc0))
    return acc.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name: str = "sep", causal: bool = True,
                   scale: Optional[float] = None, layout: str = "zigzag"):
    """Global-view entry: q,k,v [batch, seq, heads, head_dim] sharded along seq
    on ``axis_name``; batch may be sharded on dp/fsdp, heads on tp.

    ``layout='zigzag'`` (default, causal only) rebalances causal work across
    ranks by permuting the sequence into 2n stripes before the ring and back
    after — GSPMD lowers the permutation to collectives. ``'contiguous'``
    skips the permutation but the last rank does n× the first's FLOPs."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    from ..distributed.auto_parallel.logical_sharding import logical_to_spec

    qspec = logical_to_spec(("batch", "seq", "heads", None), mesh)
    kspec = logical_to_spec(("batch", "seq", "kv_heads", None), mesh)
    n = int(mesh.shape[axis_name])
    s_global = q.shape[1]

    use_zigzag = (layout == "zigzag" and causal and n > 1
                  and s_global % (2 * n) == 0)
    if use_zigzag:
        perm = jnp.asarray(zigzag_perm(s_global, n))
        inv = jnp.asarray(zigzag_inverse(s_global, n))
        q, k, v = q[:, perm], k[:, perm], v[:, perm]
        f = jax.shard_map(
            lambda a, b, c: _ring_body_zigzag(a, b, c, axis_name,
                                              float(scale), n),
            mesh=mesh, in_specs=(qspec, kspec, kspec), out_specs=qspec,
            check_vma=False)
        return f(q, k, v)[:, inv]
    f = jax.shard_map(
        lambda a, b, c: _ring_body_contiguous(a, b, c, axis_name, causal,
                                              float(scale), n),
        mesh=mesh, in_specs=(qspec, kspec, kspec), out_specs=qspec,
        check_vma=False)
    return f(q, k, v)
