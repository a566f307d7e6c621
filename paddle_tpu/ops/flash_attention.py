"""Flash attention — Pallas TPU kernels, forward AND backward.

Replaces the reference's vendored CUDA flashattn (dynload wrapper
/root/reference/paddle/phi/backends/dynload/flashattn.cc, python surface
nn/functional/flash_attention.py:195). TPU design:

Forward:
  - grid (batch, q_heads, q_blocks, kv_blocks) — kv INNERMOST, so K/V stream
    through VMEM one [block_k, d] block per grid step and Pallas's grid
    pipeline double-buffers the next block's DMA behind the current block's
    compute. Max context is bounded by HBM, not VMEM (seq 32k+ single chip).
  - online-softmax state (acc, m, l) lives in fp32 VMEM scratch that persists
    across the kv steps of one q block; (re)initialized at kv step 0,
    finalized into out/lse at the last kv step.
  - causal: fully-masked K blocks are skipped via pl.when AND their DMA is
    elided by clamping the K/V BlockSpec index_map to the last valid block
    (Pallas skips re-fetch when consecutive steps map to the same block).
  - GQA: q-head → kv-head mapping folded into the BlockSpec index_map, so
    K/V are never materialized per-q-head (the XLA fallback repeats them)
  - train path emits logsumexp [b, h, LSE_LANES, s_q] (lanes SECOND-minor
    so the tiled HBM layout pads nothing — lanes-minor cost 16x padding) so
    backward can recompute P row-stably; inference skips the write

Backward (FlashAttention-2 style, two kernels sharing the saved lse):
  - dQ kernel: grid (b, kv_heads, q_blocks, kv_blocks), same kv
    streaming/clamping as forward; dS = P*(dP-delta), dQ accumulates in VMEM
    scratch. delta = rowsum(dO * O) is FUSED into kv step 0 (dO and O are
    already VMEM-resident there) and emitted as a lane-broadcast side output
    — no separate XLA pass over dO/O and no extra HBM round-trip for delta.
  - dK/dV kernel: grid (b, kv_heads, k_blocks, q_blocks) — q innermost so the
    fp32 VMEM accumulators persist across q steps. Causal skip is a pl.when.
    Consumes the dQ kernel's delta output.
  - GQA batching (both kernels): all `group` q-heads of one kv-head arrive in
    one head-blocked q/do/lse block and are FOLDED into the matmul M dim —
    [group, BQ, d] -> [group*BQ, d] — so each program issues one large MXU
    contraction instead of `group` small ones, and K/V blocks stream from HBM
    once per kv-head (not once per q-head).

Layouts: public API is [batch, seq, heads, head_dim] (reference layout);
kernels run on [batch, heads, seq, head_dim].
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
LSE_LANES = 8  # trailing lane dim for lse/delta storage (TPU tiling)
# folded-row cap for the GQA-batched backward kernels (see _pallas_backward;
# mutable for in-process block-size A/Bs — value read at TRACE time)
BWD_ROW_CAP = [int(os.environ.get("PADDLE_TPU_FLASH_BWD_ROWCAP", "1024"))]


def _xla_reference(q, k, v, causal, scale):
    """Plain-XLA attention used as fallback and as the VJP recompute path."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _causal_last_block(qi, block_q, offset, block_k, n_kv):
    """Index of the last kv block a causal q block attends to (clipped into
    range — BlockSpec index_maps must return valid indices even for q blocks
    with no valid keys; those programs are compute-gated off by pl.when)."""
    last_k = qi * block_q + block_q - 1 + offset
    return jnp.clip(last_k // block_k, 0, n_kv - 1)


def _make_kv_idx(causal, block_q, offset, block_k, n_kv):
    """kv-block index map component for kv-innermost grids: clamp future
    (fully-masked) blocks onto the last valid one — consecutive grid steps
    then map to the SAME block and Pallas elides the DMA."""
    def kv_idx(qi, ki):
        if not causal:
            return ki
        return jnp.minimum(ki, _causal_last_block(qi, block_q, offset,
                                                  block_k, n_kv))
    return kv_idx


def _make_q_idx(causal, block_q, offset, block_k, n_q):
    """Mirror of :func:`_make_kv_idx` for the dK/dV kernel's q-innermost
    grid: q blocks entirely BEFORE a k block (run=False there) are clamped
    onto the first valid q block, eliding their q/do/lse/delta DMAs."""
    def q_idx(ki, qi):
        if not causal:
            return qi
        first = (ki * block_k - offset) // block_q
        return jnp.maximum(qi, jnp.clip(first, 0, n_q - 1))
    return q_idx


def _fa_fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal,
                   block_q, block_k, kv_len, q_len, n_kv, with_seg=False,
                   with_rowmask=False):
    """One (batch, head, q-block, kv-block) program. K/V arrive one
    [block_k, d] block per grid step (kv innermost — Pallas double-buffers
    the next block's DMA behind this block's compute); the online-softmax
    state (acc, m, l) persists in fp32 VMEM scratch across the kv steps of a
    q block. With ``with_seg`` the first two extra refs are per-position
    segment ids ([b, s, LSE_LANES] int32) and attention is block-diagonal
    over equal segments (varlen packed batches). With ``with_rowmask`` the
    next two refs are per-KV-COLUMN row bounds ([b, h, s_kv, LSE_LANES]
    int32): q rows in [start[col], end[col]) are masked (the reference's
    flashmask LT masks, nn/functional/flash_attention.py:1098)."""
    if with_seg:
        qseg_ref, kseg_ref = refs[0], refs[1]
        refs = refs[2:]
    if with_rowmask:
        start_ref, end_ref = refs[0], refs[1]
        refs = refs[2:]
    o_ref = refs[0]
    # refs after o_ref: [lse_ref (train path only)] + [acc_sc, m_sc, l_sc]
    if len(refs) == 5:
        lse_ref = refs[1]
        acc_sc, m_sc, l_sc = refs[2], refs[3], refs[4]
    else:
        lse_ref = None
        acc_sc, m_sc, l_sc = refs[1], refs[2], refs[3]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # End-aligned causal offset: q row i attends k cols <= i + (kv_len - q_len),
    # matching _xla_reference's tril(k=kl-ql) (kv-cache style when kv > q).
    offset = kv_len - q_len
    run = True
    if causal:
        # blocks entirely in the future: no compute (their DMA is already
        # elided by the clamped index_map)
        run = qi * block_q + block_q - 1 + offset >= ki * block_k

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [BQ, d]
        kb = k_ref[0, 0].astype(jnp.float32)              # [BK, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
        if with_seg:
            qs = qseg_ref[0][:, 0]                        # [BQ]
            ks = kseg_ref[0][:, 0]                        # [BK]
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        if with_rowmask:
            st = start_ref[0, 0][:, 0]                    # [BK]
            en = end_ref[0, 0][:, 0]
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            masked = (rows >= st[None, :]) & (rows < en[None, :])
            s = jnp.where(masked, NEG_INF, s)
        m = m_sc[...][:, :1]                              # [BQ, 1]
        l = l_sc[...][:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(ki == n_kv - 1)
    def _():
        l = l_sc[...][:, :1]
        m = m_sc[...][:, :1]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # lse (train path only — the primal/inference kernel skips the
            # write) in units of the SCALED logits; rows with no valid keys
            # get NEG_INF. Stored with LSE_LANES trailing lanes (TPU block
            # constraint: the last block dim must be 128-divisible or equal
            # the array dim — 8 lanes beats the library kernel's 128-lane
            # padding on HBM traffic 16x).
            lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
            lse_ref[0, 0] = jnp.broadcast_to(jnp.swapaxes(lse, 0, 1),
                                             lse_ref.shape[2:])


def _seg_lanes(seg, s):
    """[b, s] int32 -> [b, s, LSE_LANES] (TPU block tiling)."""
    seg = seg.astype(jnp.int32)
    return jnp.broadcast_to(seg[..., None], seg.shape + (LSE_LANES,))


def _pallas_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                    with_lse=True, q_seg=None, kv_seg=None,
                    row_start=None, row_end=None):
    """q,k,v in [b, s, h, d]. Returns (out [b,s,h,d],
    lse [b, hq, LSE_LANES, s_q] fp32 (lane-broadcast, lanes second-minor so
    the tiled HBM layout pads nothing) — or None when with_lse=False, the
    primal/inference path, which skips the lse HBM write entirely)."""
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, hq, d = q.shape
    _, s_kv, hkv, _ = k.shape
    group = hq // hkv
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    n_kv = s_kv // block_k
    grid = (b, hq, s_q // block_q, n_kv)
    offset = s_kv - s_q
    _kv_idx = _make_kv_idx(causal, block_q, offset, block_k, n_kv)

    with_seg = q_seg is not None
    with_rowmask = row_start is not None
    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=s_kv, q_len=s_q, n_kv=n_kv,
        with_seg=with_seg, with_rowmask=with_rowmask)
    out_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct(qt.shape, q.dtype)]
    if with_lse:
        # lanes SECOND-minor ([b, h, LANES, s]): the (8,128)-tiled HBM layout
        # then pads nothing, vs 16x expansion for a lanes-minor [.., s, 8]
        # buffer (measured 120MB of padding per 8MB of lse on a 2048-seq
        # batch-8 run — and remat keeps one per layer alive all backward)
        out_specs.append(pl.BlockSpec((1, 1, LSE_LANES, block_q),
                                      lambda bi, hi, qi, ki: (bi, hi, 0, qi)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, hq, LSE_LANES, s_q), jnp.float32))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi // group,
                                             _kv_idx(qi, ki), 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi // group,
                                             _kv_idx(qi, ki), 0)),
    ]
    operands = [qt, kt, vt]
    if with_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, block_k, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, _kv_idx(qi, ki), 0)),
        ]
        operands += [_seg_lanes(q_seg, s_q), _seg_lanes(kv_seg, s_kv)]
    if with_rowmask:
        # bounds are per kv-HEAD [b, hkv, s_kv]; q-head hi maps via hi//group
        hm = row_start.shape[1]
        in_specs += [
            pl.BlockSpec((1, 1, block_k, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, (hi // group) % hm,
                                                 _kv_idx(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, (hi // group) % hm,
                                                 _kv_idx(qi, ki), 0)),
        ]
        operands += [_seg_lanes(row_start.astype(jnp.int32), s_kv),
                     _seg_lanes(row_end.astype(jnp.int32), s_kv)]
    with jax.named_scope("pt_flash_fwd"):
        res = pl.pallas_call(
            kernel,
            name="pt_flash_fwd",
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),          # acc
                pltpu.VMEM((block_q, LSE_LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, LSE_LANES), jnp.float32),  # running sum
            ],
            interpret=interpret,
        )(*operands)
    lse = res[1] if with_lse else None
    return jnp.swapaxes(res[0], 1, 2), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _fold_heads(x):
    """[group, rows, d] -> [group*rows, d] (contiguous collapse of the two
    leading dims — free on TPU, rows stay sublane-major)."""
    g, r, d = x.shape
    return x.reshape(g * r, d)


def _fold_lanes(ref_slice):
    """[group, LANES, BQ] lane-broadcast lse/delta block -> [group*BQ, 1]
    column (one small [1, BQ] -> [BQ, 1] relayout per group, batched)."""
    g, _, bq = ref_slice.shape
    col = jnp.swapaxes(ref_slice[:, :1, :], 1, 2)          # [g, BQ, 1]
    return col.reshape(g * bq, 1)


def _row_positions(qi, block_q, group, block_k):
    """Absolute q positions for the folded [group*BQ, BK] score rows: row r
    of the fold is q row (r % BQ) of q block qi (heads repeat the rows)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (group * block_q, block_k), 0)
    return qi * block_q + r % block_q


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *refs,
                      scale, causal, block_q, block_k, kv_len, q_len, n_kv,
                      group, with_glse=False, with_seg=False,
                      with_rowmask=False):
    """dQ for one (batch, KV head, q_block, kv_block); K/V stream through the
    innermost grid dim like forward, fetched ONCE per kv-head (all `group`
    q-heads fold into the matmul M dim). delta = rowsum(dO*O) [− l̄] is
    computed at kv step 0 (dO/O are VMEM-resident) into scratch and emitted
    as a lane-broadcast side output for the dK/dV kernel — the separate XLA
    delta pass and its HBM round-trip are gone."""
    if with_glse:
        glse_ref = refs[0]
        refs = refs[1:]
    if with_seg:
        qseg_ref, kseg_ref = refs[0], refs[1]
        refs = refs[2:]
    if with_rowmask:
        start_ref, end_ref = refs[0], refs[1]
        refs = refs[2:]
    dq_ref, delta_ref, dq_sc, delta_sc = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        do0 = _fold_heads(do_ref[0].astype(jnp.float32))   # [G*BQ, d]
        o0 = _fold_heads(o_ref[0].astype(jnp.float32))
        delta = jnp.sum(do0 * o0, axis=-1, keepdims=True)  # [G*BQ, 1]
        if with_glse:
            # ring attention's lse cotangent folds into delta: ds = p·(dp−δ+l̄)
            delta = delta - _fold_lanes(glse_ref[0])
        dq_sc[...] = jnp.zeros_like(dq_sc)
        delta_sc[...] = jnp.broadcast_to(delta, delta_sc.shape)
        # delta output is lanes-second-minor [group, LANES, BQ] like lse
        dcol = jnp.swapaxes(delta.reshape(group, block_q, 1), 1, 2)
        delta_ref[0] = jnp.broadcast_to(dcol, delta_ref.shape[1:])

    offset = kv_len - q_len
    run = True
    if causal:
        run = qi * block_q + block_q - 1 + offset >= ki * block_k

    @pl.when(run)
    def _():
        q = _fold_heads(q_ref[0].astype(jnp.float32))      # [G*BQ, d]
        do = _fold_heads(do_ref[0].astype(jnp.float32))
        lse = _fold_lanes(lse_ref[0])                      # [G*BQ, 1]
        delta = delta_sc[...][:, :1]                       # [G*BQ, 1]
        kb = k_ref[0, 0].astype(jnp.float32)               # [BK, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = _row_positions(qi, block_q, group, block_k)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (group * block_q, block_k), 1)
            s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
        if with_seg:
            qs = qseg_ref[0][:, 0]                         # [BQ]
            ks = kseg_ref[0][:, 0]
            qs = jnp.broadcast_to(qs[None, :], (group, block_q)).reshape(-1)
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        if with_rowmask:
            st = start_ref[0, 0][:, 0]
            en = end_ref[0, 0][:, 0]
            rows = _row_positions(qi, block_q, group, block_k)
            s = jnp.where((rows >= st[None, :]) & (rows < en[None, :]),
                          NEG_INF, s)
        # rows with no valid keys store lse = NEG_INF; exp(s - lse) would give
        # p = 1 there (s is NEG_INF too) — force those rows to zero instead
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [G*BQ, BK]
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # [G*BQ, BK]
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[0] = dq_sc[...].reshape(group, block_q, -1).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       *refs, scale, causal,
                       block_q, block_k, kv_len, q_len, group, with_seg=False,
                       with_rowmask=False):
    """dK/dV for one (batch, kv_head, k_block); q_blocks is the innermost grid
    dim so dk_acc/dv_acc VMEM scratch persists and accumulates across q steps.
    All `group` q-heads of this kv-head arrive in one head-blocked q block and
    fold into the contraction dims: one [G*BQ, BK] score matrix, dV/dK as
    single G*BQ-deep contractions (vs `group` small ones)."""
    if with_seg:
        qseg_ref, kseg_ref = refs[0], refs[1]
        refs = refs[2:]
    if with_rowmask:
        start_ref, end_ref = refs[0], refs[1]
        refs = refs[2:]
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    offset = kv_len - q_len

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: skip q blocks entirely in the past of this k block
    run = True
    if causal:
        run = qi * block_q + block_q - 1 + offset >= ki * block_k

    @pl.when(run)
    def _():
        kb = k_ref[0, 0].astype(jnp.float32)               # [BK, d]
        vb = v_ref[0, 0].astype(jnp.float32)               # [BK, d]
        q = _fold_heads(q_ref[0].astype(jnp.float32))      # [G*BQ, d]
        do = _fold_heads(do_ref[0].astype(jnp.float32))
        lse = _fold_lanes(lse_ref[0])                      # [G*BQ, 1]
        delta = _fold_lanes(delta_ref[0])
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = _row_positions(qi, block_q, group, block_k)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (group * block_q, block_k), 1)
            s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
        if with_seg:
            qsg = qseg_ref[0][:, 0]
            ksg = kseg_ref[0][:, 0]
            qsg = jnp.broadcast_to(qsg[None, :], (group, block_q)).reshape(-1)
            s = jnp.where(qsg[:, None] == ksg[None, :], s, NEG_INF)
        if with_rowmask:
            st = start_ref[0, 0][:, 0]
            en = end_ref[0, 0][:, 0]
            rows = _row_positions(qi, block_q, group, block_k)
            s = jnp.where((rows >= st[None, :]) & (rows < en[None, :]),
                          NEG_INF, s)
        # see dq kernel: fully-masked rows (lse == NEG_INF) must give p = 0
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [G*BQ, BK]
        # dV += P^T · dO — one G*BQ-deep contraction
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # [G*BQ, BK]
        # dK += dS^T · Q
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _pallas_backward(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                     interpret, g_lse=None, q_seg=None, kv_seg=None,
                     row_start=None, row_end=None):
    """All arrays in the public [b, s, h, d] layout.

    lse is the forward's [b, hq, LSE_LANES, s_q] output (lanes second-minor,
    value broadcast across the lane dim).
    ``g_lse`` [b, hq, s_q] is an optional cotangent on the lse OUTPUT (ring
    attention's merge differentiates through it): with l̄ present the score
    gradient becomes ds = p·(dp − delta + l̄), i.e. l̄ just shifts delta."""
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, hq, d = q.shape
    _, s_kv, hkv, _ = k.shape
    group = hq // hkv

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot = jnp.swapaxes(do, 1, 2)
    ot = jnp.swapaxes(o, 1, 2)

    n_kv = s_kv // block_k
    offset = s_kv - s_q

    with_glse = g_lse is not None
    with_seg = q_seg is not None
    with_rowmask = row_start is not None
    seg_ops = ([_seg_lanes(q_seg, s_q), _seg_lanes(kv_seg, s_kv)]
               if with_seg else [])
    if with_rowmask:
        seg_ops += [_seg_lanes(row_start.astype(jnp.int32), s_kv),
                    _seg_lanes(row_end.astype(jnp.int32), s_kv)]
        hm = row_start.shape[1]

    # GQA folding multiplies the score-matrix rows by `group`; bound the
    # folded [rows, block_k] f32 score/p/dp/ds working set (it must fit the
    # ~16MB scoped-VMEM stack: 2048 rows x 512 cols OOMed). First shrink the
    # q block toward rows <= 1024 (still ≥128: block_q is minor in the lse
    # layout), then — for very wide groups (MQA, group > 8) where even
    # bq=128 exceeds the row cap — shrink the backward's k block so
    # rows * block_k stays <= 1024 * 512.
    # on-chip A/B (benchmarks/flash_block_ab.py, GQA 16/4 d128): folded-row
    # cap 1024 is fastest at seq 4096 (33.6 vs 25.2 TF/s for 2048), while
    # long context flips — at seq 16384 cap 2048 (bq 512, bk auto-halved to
    # 256) wins 67.4 vs 64.7 TF/s. Default: 1024 short, 2048 at >= 8k.
    row_cap = BWD_ROW_CAP[0]
    if s_q >= 8192 and row_cap == 1024:
        row_cap = 2048
    bq_dq = block_q
    for c in (512, 256, 128):
        if group * c <= row_cap and c <= block_q and s_q % c == 0:
            bq_dq = c
            break
    else:
        if 128 <= block_q and s_q % 128 == 0:
            bq_dq = 128
    bk_dq = block_k
    while (group * bq_dq * bk_dq > row_cap * 512 and bk_dq > 128
           and bk_dq % 2 == 0 and s_kv % (bk_dq // 2) == 0):
        bk_dq //= 2

    # ---- dQ (+ fused delta side output) ----
    # grid is over KV heads: all `group` q-heads of a kv-head are handled by
    # one program (folded into the matmul M dim), so K/V stream once per
    # kv-head instead of once per q-head.
    n_kv_b = s_kv // bk_dq
    grid_dq = (b, hkv, s_q // bq_dq, n_kv_b)
    dq_kernel = functools.partial(
        _fa_bwd_dq_kernel, scale=scale, causal=causal,
        block_q=bq_dq, block_k=bk_dq, kv_len=s_kv, q_len=s_q, n_kv=n_kv_b,
        group=group, with_glse=with_glse, with_seg=with_seg,
        with_rowmask=with_rowmask)
    _kv_idx_dq = _make_kv_idx(causal, bq_dq, offset, bk_dq, n_kv_b)
    _qb = pl.BlockSpec((1, group, bq_dq, d),
                       lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    _qlanes = pl.BlockSpec((1, group, LSE_LANES, bq_dq),
                           lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    _kvb = pl.BlockSpec((1, 1, bk_dq, d),
                        lambda bi, hi, qi, ki: (bi, hi,
                                                _kv_idx_dq(qi, ki), 0))
    dq_in_specs = [_qb, _kvb, _kvb, _qb, _qb, _qlanes]
    dq_ops = [qt, kt, vt, dot, ot, lse]
    if with_glse:
        dq_in_specs.append(_qlanes)
        glse_lanes = jnp.broadcast_to(
            g_lse.astype(jnp.float32)[:, :, None, :],
            g_lse.shape[:2] + (LSE_LANES,) + g_lse.shape[2:])
        dq_ops.append(glse_lanes)
    if with_seg:
        dq_in_specs += [
            pl.BlockSpec((1, bq_dq, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, bk_dq, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, _kv_idx_dq(qi, ki), 0)),
        ]
    if with_rowmask:
        dq_in_specs += [
            pl.BlockSpec((1, 1, bk_dq, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, hi % hm,
                                                 _kv_idx_dq(qi, ki), 0)),
            pl.BlockSpec((1, 1, bk_dq, LSE_LANES),
                         lambda bi, hi, qi, ki: (bi, hi % hm,
                                                 _kv_idx_dq(qi, ki), 0)),
        ]
    with jax.named_scope("pt_flash_dq"):
        dq, delta = pl.pallas_call(
            dq_kernel,
            name="pt_flash_dq",
            grid=grid_dq,
            in_specs=dq_in_specs,
            out_specs=[_qb, _qlanes],
            out_shape=[
                jax.ShapeDtypeStruct(qt.shape, q.dtype),
                jax.ShapeDtypeStruct((b, hq, LSE_LANES, s_q), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((group * bq_dq, d), jnp.float32),          # dq acc
                pltpu.VMEM((group * bq_dq, LSE_LANES), jnp.float32),  # delta
            ],
            interpret=interpret,
        )(*dq_ops, *seg_ops)

    # ---- dK / dV ----
    # q-heads blocked by `group` so one program sees every q-head of its
    # kv-head; q_blocks innermost so VMEM accumulators carry across q steps.
    _q_idx = _make_q_idx(causal, bq_dq, offset, bk_dq, s_q // bq_dq)
    grid_dkv = (b, hkv, s_kv // bk_dq, s_q // bq_dq)
    dkv_kernel = functools.partial(
        _fa_bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=bq_dq, block_k=bk_dq, kv_len=s_kv, q_len=s_q, group=group,
        with_seg=with_seg, with_rowmask=with_rowmask)
    dkv_in_specs = [
        pl.BlockSpec((1, group, bq_dq, d),
                     lambda bi, hi, ki, qi: (bi, hi, _q_idx(ki, qi), 0)),
        pl.BlockSpec((1, 1, bk_dq, d),
                     lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        pl.BlockSpec((1, 1, bk_dq, d),
                     lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        pl.BlockSpec((1, group, bq_dq, d),
                     lambda bi, hi, ki, qi: (bi, hi, _q_idx(ki, qi), 0)),
        pl.BlockSpec((1, group, LSE_LANES, bq_dq),
                     lambda bi, hi, ki, qi: (bi, hi, 0, _q_idx(ki, qi))),
        pl.BlockSpec((1, group, LSE_LANES, bq_dq),
                     lambda bi, hi, ki, qi: (bi, hi, 0, _q_idx(ki, qi))),
    ]
    if with_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, bq_dq, LSE_LANES),
                         lambda bi, hi, ki, qi: (bi, _q_idx(ki, qi), 0)),
            pl.BlockSpec((1, bk_dq, LSE_LANES),
                         lambda bi, hi, ki, qi: (bi, ki, 0)),
        ]
    if with_rowmask:
        dkv_in_specs += [
            pl.BlockSpec((1, 1, bk_dq, LSE_LANES),
                         lambda bi, hi, ki, qi: (bi, hi % hm, ki, 0)),
            pl.BlockSpec((1, 1, bk_dq, LSE_LANES),
                         lambda bi, hi, ki, qi: (bi, hi % hm, ki, 0)),
        ]
    with jax.named_scope("pt_flash_dkv"):
        dk, dv = pl.pallas_call(
            dkv_kernel,
            name="pt_flash_dkv",
            grid=grid_dkv,
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bk_dq, d),
                             lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
                pl.BlockSpec((1, 1, bk_dq, d),
                             lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(kt.shape, k.dtype),
                jax.ShapeDtypeStruct(vt.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk_dq, d), jnp.float32),
                pltpu.VMEM((bk_dq, d), jnp.float32),
            ],
            interpret=interpret,
        )(qt, kt, vt, dot, lse, delta, *seg_ops)

    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


# ---------------------------------------------------------------------------
# dispatch + custom_vjp
# ---------------------------------------------------------------------------

def _use_pallas(q, k, block_q, block_k, interpret):
    # shape guards apply in interpret mode too — a non-divisible seq would leave
    # output rows unwritten / drop kv tokens silently. block_q additionally
    # sits in the MINOR dim of the lse/delta blocks ([.., LANES, block_q]),
    # so it must be 128-divisible or the whole sequence (Mosaic tiling).
    s_q, s_kv = q.shape[1], k.shape[1]
    shapes_ok = (s_q % block_q == 0 and s_kv % block_k == 0
                 and (block_q % 128 == 0 or block_q == s_q)
                 and q.shape[2] % k.shape[2] == 0)
    if interpret:
        return shapes_ok
    if jax.default_backend() != "tpu":
        return False
    return shapes_ok and q.shape[3] in (64, 128, 256)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    if _use_pallas(q, k, block_q, block_k, interpret):
        # primal (inference) path: skip the lse output entirely
        return _pallas_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret, with_lse=False)[0]
    return _xla_reference(q, k, v, causal, scale)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if _use_pallas(q, k, block_q, block_k, interpret):
        from jax.ad_checkpoint import checkpoint_name

        out, lse = _pallas_forward(q, k, v, causal, scale, block_q, block_k,
                                   interpret)
        # named so a remat policy can SAVE these residuals — backward then
        # skips re-running the flash forward kernel (save-attention-out remat)
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return out, (q, k, v, out, lse)
    return _xla_reference(q, k, v, causal, scale), (q, k, v, None, None)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    if lse is not None:
        return _pallas_backward(q, k, v, o, lse, g, causal, scale,
                                block_q, block_k, interpret)
    _, vjp = jax.vjp(lambda a, b, c: _xla_reference(a, b, c, causal, scale),
                     q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _xla_reference_lse(q, k, v, causal, scale):
    """XLA fallback returning (out, lse [b, hq, s_q] fp32 of SCALED logits)."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, NEG_INF)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)   # [b, h, q]
    probs = jnp.exp(logits - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q, k, v, causal, scale, block_q, block_k,
                             interpret):
    """(out [b,s,h,d], lse [b, hq, s_q] fp32) — differentiable INCLUDING the
    lse output (ring attention's online-softmax merge needs d/dlse; the
    backward folds the lse cotangent into the delta term: ds = p·(dp−δ+l̄))."""
    if _use_pallas(q, k, block_q, block_k, interpret):
        out, lse4 = _pallas_forward(q, k, v, causal, scale, block_q, block_k,
                                    interpret, with_lse=True)
        return out, lse4[:, :, 0, :]
    return _xla_reference_lse(q, k, v, causal, scale)


def _fwl_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if _use_pallas(q, k, block_q, block_k, interpret):
        out, lse4 = _pallas_forward(q, k, v, causal, scale, block_q, block_k,
                                    interpret, with_lse=True)
        return (out, lse4[:, :, 0, :]), (q, k, v, out, lse4)
    out, lse = _xla_reference_lse(q, k, v, causal, scale)
    return (out, lse), (q, k, v, None, None)


def _fwl_bwd(causal, scale, block_q, block_k, interpret, res, cots):
    q, k, v, o, lse4 = res
    g_out, g_lse = cots
    if lse4 is not None:
        return _pallas_backward(q, k, v, o, lse4, g_out, causal, scale,
                                block_q, block_k, interpret, g_lse=g_lse)
    _, vjp = jax.vjp(
        lambda a, b, c: _xla_reference_lse(a, b, c, causal, scale), q, k, v)
    return vjp((g_out, g_lse))


flash_attention_with_lse.defvjp(_fwl_fwd, _fwl_bwd)


def _tuned_block(n: int, kv: bool = False) -> int:
    """Largest of 512/256/128 dividing n (v5e-profiled: 512 blocks reach
    ~25 TF/s fwd+bwd at head_dim 128 vs ~8 TF/s at the library defaults).
    Long-context KV side: 1024 at seq >= 8192 — halves the kv grid steps and
    their DMA issue overhead (on-chip A/B at 16k GQA 16/4: 50.4 vs 54.2 ms
    fwd+bwd, +7.5%; the backward's VMEM guard re-halves its own k block, so
    only the forward stream widens). Sequences shorter than 128 use one
    whole-sequence block; longer sequences not divisible by 128 get the
    default block, which fails the divisibility guard in _use_pallas and
    routes to the XLA fallback (a whole-sequence block there would
    materialize [s, s] scores in VMEM)."""
    if kv and n >= 8192 and n % 1024 == 0:
        return 1024
    for b in (512, 256, 128):
        if n % b == 0:
            return b
    return n if n < 128 else DEFAULT_BLOCK_Q


def _jax_tuned_flash(q, k, v, causal, scale):
    """jax's library TPU flash kernel — kept as an A/B comparison path
    (PADDLE_TPU_FLASH_IMPL=jaxlib). MHA, q_len == kv_len only."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention as jfa)

    qh = jnp.swapaxes(q, 1, 2)  # -> [b, h, s, d]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    bq = _tuned_block(qh.shape[2])
    bk = _tuned_block(kh.shape[2])
    bs = BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)
    out = jfa(qh, kh, vh, causal=causal, sm_scale=float(scale),
              block_sizes=bs)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = 0, block_k: int = 0,
                    interpret: bool = False):
    """q,k,v: [batch, seq, heads, head_dim] (reference layout,
    nn/functional/flash_attention.py:195). Returns same layout/dtype as q.

    Production path is the IN-REPO Pallas kernel pair (fwd with logsumexp +
    FlashAttention-2 backward), covering MHA, GQA (q-head→kv-head folded into
    BlockSpec index maps — K/V never repeated), and kv-cache decode
    (q_len != kv_len via END-aligned causal masking, tril(k=kv-q)).
    Set PADDLE_TPU_FLASH_IMPL=jaxlib to A/B against jax's library kernel
    (MHA equal-length shapes only). Non-divisible / odd shapes fall back to
    the XLA reference implementation."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    impl = os.environ.get("PADDLE_TPU_FLASH_IMPL", "")
    if (impl == "jaxlib" and not interpret and jax.default_backend() == "tpu"
            and q.shape[1] == k.shape[1] and q.shape[1] % 128 == 0
            and q.shape[-1] in (64, 128, 256)
            and q.shape[2] == k.shape[2]):
        return _jax_tuned_flash(q, k, v, causal, scale)
    bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
    bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
    return _flash(q, k, v, causal, float(scale), bq, bk, interpret)


# ---------------------------------------------------------------------------
# varlen (packed, segment-masked) attention
# ---------------------------------------------------------------------------

def _xla_varlen_reference(q, k, v, q_seg, kv_seg, causal, scale):
    """Dense-mask fallback: attention restricted to equal segment ids."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    mask = q_seg[:, None, :, None] == kv_seg[:, None, None, :]
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = mask & jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
    logits = jnp.where(mask, logits, NEG_INF)
    # fully-masked rows (padding segments) -> zero output, not NaN
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-30), vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _seg_zero_cot(seg):
    import numpy as _np

    return _np.zeros(seg.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention_varlen(q, k, v, q_seg, kv_seg, causal=True, scale=None,
                           block_q=0, block_k=0, interpret=False):
    """Packed variable-length attention as a KERNEL (reference:
    nn/functional/flash_attention.py:792 varlen over the CUDA varlen kernels).

    q/k/v: [b, s, h, d]; q_seg/kv_seg: [b, s] int32 segment ids — attention is
    block-diagonal over equal segments (plus causal within each segment, since
    packed positions are monotone per segment). Runs the in-repo Pallas flash
    kernels fwd+bwd with the segment mask folded into the score masking; CPU /
    non-divisible shapes take a dense-mask XLA path."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
    bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
    if _use_pallas(q, k, bq, bk, interpret):
        return _pallas_forward(q, k, v, causal, float(scale), bq, bk,
                               interpret, with_lse=False,
                               q_seg=q_seg, kv_seg=kv_seg)[0]
    return _xla_varlen_reference(q, k, v, q_seg, kv_seg, causal, float(scale))


def _fav_fwd(q, k, v, q_seg, kv_seg, causal, scale, block_q, block_k,
             interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
    bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
    if _use_pallas(q, k, bq, bk, interpret):
        out, lse = _pallas_forward(q, k, v, causal, float(scale), bq, bk,
                                   interpret, with_lse=True,
                                   q_seg=q_seg, kv_seg=kv_seg)
        return out, (q, k, v, q_seg, kv_seg, out, lse)
    out = _xla_varlen_reference(q, k, v, q_seg, kv_seg, causal, float(scale))
    return out, (q, k, v, q_seg, kv_seg, None, None)


def _fav_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, q_seg, kv_seg, o, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if lse is not None:
        bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
        bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
        dq, dk, dv = _pallas_backward(q, k, v, o, lse, g, causal, float(scale),
                                      bq, bk, interpret,
                                      q_seg=q_seg, kv_seg=kv_seg)
        return dq, dk, dv, _seg_zero_cot(q_seg), _seg_zero_cot(kv_seg)
    _, vjp = jax.vjp(
        lambda a, b, c: _xla_varlen_reference(a, b, c, q_seg, kv_seg, causal,
                                              float(scale)), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, _seg_zero_cot(q_seg), _seg_zero_cot(kv_seg)


flash_attention_varlen.defvjp(_fav_fwd, _fav_bwd)


# ---------------------------------------------------------------------------
# flashmask (per-column row-bound sparse masks) attention
# ---------------------------------------------------------------------------

def _xla_rowmask_reference(q, k, v, row_start, row_end, causal, scale):
    """Dense fallback: q row r masked from kv col c iff start[c] <= r < end[c].
    row bounds: [b, hm, s_kv] with hm in {1, kv_heads}."""
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    hq, hkv = qh.shape[1], kh.shape[1]
    if hkv != hq:
        rep = hq // hkv
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    ql, kl = logits.shape[-2], logits.shape[-1]
    hm = row_start.shape[1]
    st = jnp.repeat(row_start, hq // hm, axis=1) if hm not in (1,) else row_start
    en = jnp.repeat(row_end, hq // hm, axis=1) if hm not in (1,) else row_end
    rows = jnp.arange(ql)[None, None, :, None]
    blocked = (rows >= st[:, :, None, :]) & (rows < en[:, :, None, :])
    keep = ~blocked
    if causal:
        keep = keep & jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
    logits = jnp.where(keep, logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-30), vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def flash_attention_rowmask(q, k, v, row_start, row_end, causal=True,
                            scale=None, block_q=0, block_k=0,
                            interpret=False):
    """Flashmask attention as a KERNEL (reference:
    nn/functional/flash_attention.py:1098 flashmask_attention): per-KV-column
    row bounds [b, hm, s_kv] (hm in {1, kv_heads}) mask q rows in
    [start[c], end[c]) — the reference's LT sparse-mask encoding — streamed
    through the Pallas flash kernels fwd+bwd. CPU / odd shapes take a dense
    path."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
    bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
    if _use_pallas(q, k, bq, bk, interpret):
        return _pallas_forward(q, k, v, causal, float(scale), bq, bk,
                               interpret, with_lse=False,
                               row_start=row_start, row_end=row_end)[0]
    return _xla_rowmask_reference(q, k, v, row_start, row_end, causal,
                                  float(scale))


def _far_fwd(q, k, v, row_start, row_end, causal, scale, block_q, block_k,
             interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
    bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
    if _use_pallas(q, k, bq, bk, interpret):
        out, lse = _pallas_forward(q, k, v, causal, float(scale), bq, bk,
                                   interpret, with_lse=True,
                                   row_start=row_start, row_end=row_end)
        return out, (q, k, v, row_start, row_end, out, lse)
    out = _xla_rowmask_reference(q, k, v, row_start, row_end, causal,
                                 float(scale))
    return out, (q, k, v, row_start, row_end, None, None)


def _far_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, row_start, row_end, o, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if lse is not None:
        bq = min(block_q or _tuned_block(q.shape[1]), q.shape[1])
        bk = min(block_k or _tuned_block(k.shape[1], kv=True), k.shape[1])
        dq, dk, dv = _pallas_backward(q, k, v, o, lse, g, causal,
                                      float(scale), bq, bk, interpret,
                                      row_start=row_start, row_end=row_end)
    else:
        _, vjp = jax.vjp(
            lambda a, b, c: _xla_rowmask_reference(
                a, b, c, row_start, row_end, causal, float(scale)), q, k, v)
        dq, dk, dv = vjp(g)
    return dq, dk, dv, _seg_zero_cot(row_start), _seg_zero_cot(row_end)


flash_attention_rowmask.defvjp(_far_fwd, _far_bwd)


# Back-compat name used by nn.functional
flash_attention_fwd = flash_attention
