"""Paged (block) KV-cache attention — Pallas TPU kernels for batched serving.

TPU-native replacement for the reference's paged serving kernels
(/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
python surface python/paddle/incubate/nn/functional/block_multihead_attention.py):
KV lives in a pool of fixed-size pages; each sequence owns a list of pages via a
block table, so cache memory is bounded by total tokens, not batch × max_len.

Layouts (reference block_multihead_attention):
  k_cache/v_cache: [num_pages, kv_heads, page_size, head_dim]
  block_tables:    [batch, pages_per_seq] int32 (-1 = unassigned)
  context_lens:    [batch] int32 — tokens already in cache (incl. current step)

Decode kernel design (measured 435 GB/s-class architecture, v5e):
  - grid (batch, kv_heads, seq_chunks); each chunk DMAs G pages of ONE kv head
    HBM→VMEM. The chunk loop is a *grid* dimension, so double buffering runs
    across grid steps: an SMEM buffer index persists, and each step prefetches
    the NEXT VALID (b, h, chunk) step's pages while computing its own.
  - context lengths arrive via scalar prefetch; chunks past a sequence's
    length are skipped entirely (no DMA, no compute).
  - online softmax in fp32 with VMEM carry across chunks; GQA computes all
    `group` q-heads of the kv head in one [group, G*page] block.
"""

from __future__ import annotations

import collections
import functools
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: int8 KV block format: symmetric absmax quantization, q = round(x / step)
#: with step = scale / KV_QMAX — the same scale convention as
#: quantization.PerChannelAbsmaxObserver / ConvertedLinear (scale == absmax,
#: qmax = 2^(bits-1) - 1), applied per (page, kv_head) block.
KV_QMAX = 127


# ---------------------------------------------------------------------------
# int8 paged-KV block format (opt-in — serving.KVCacheConfig(dtype="int8"))
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class QuantizedKVPool:
    """One side (k or v) of a paged-KV pool in the int8 block format.

    ``data`` [num_pages, kv_heads, page, head_dim] int8 and ``scale``
    [num_pages, kv_heads] float32 — one absmax scale per (page, kv_head)
    block, living beside the pool (reusing the
    ``quantization.PerChannelAbsmaxObserver`` convention: scale == absmax,
    stored value = round(x / (scale / KV_QMAX))). Registered as a jax
    pytree, so it flows through jit/scan carries and ``donate_argnums``
    exactly like the plain array it replaces; ``.shape``/``.dtype``
    delegate to ``data`` so pool-geometry probes (page size, head counts,
    codec compatibility checks) keep working unchanged.

    Write paths quantize on append (:func:`append_paged_kv`): the block
    scale is grown by scatter-max with the incoming tokens' absmax and
    already-stored values are REquantized under the grown scale (one
    elementwise pass over the pool — ratio is 1.0 for untouched blocks, so
    their stored bytes are bit-stable through ``round``). Read paths
    dequantize in the gather (:func:`paged_decode_attention` /
    :func:`paged_prefill_attention` / :func:`paged_verify_attention`), so
    attention math stays fp32. Pool bytes drop ~itemsize-fold (bf16 -> int8
    halves them), doubling effective slots and radix prefix-cache reach at
    equal memory. The Pallas decode kernel does not yet carry the dequant
    (int8 routes to the XLA reference path — open TPU-kernel work)."""

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    def __repr__(self):
        return (f"QuantizedKVPool(shape={tuple(self.data.shape)}, "
                f"dtype={self.data.dtype})")


def kv_absmax(x):
    """Per-(token, kv_head) absmax of new k/v rows ``x`` [n, kv_heads, d] —
    the head_dim reduction of ``PerChannelAbsmaxObserver`` math, feeding
    the per-block scatter-max on append."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)


def quantize_kv(x, scale):
    """Symmetric int8 quantization of ``x`` with per-channel ``scale``
    (broadcast against ``x``): round(x / (scale / KV_QMAX)) clipped to
    +-KV_QMAX. ``scale == 0`` blocks hold only zeros by construction (a
    scale is the absmax of everything ever written)."""
    step = scale.astype(jnp.float32) / KV_QMAX
    safe = jnp.where(step > 0, step, 1.0)
    q = jnp.round(x.astype(jnp.float32) / safe)
    return jnp.clip(q, -KV_QMAX, KV_QMAX).astype(jnp.int8)


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv` (fp32): q * (scale / KV_QMAX).
    Per-block dequant error is bounded by step/2 = scale / (2 * KV_QMAX)
    per quantization event; requant-on-grow events compound boundedly
    (tests pin the end-to-end bound)."""
    return q.astype(jnp.float32) * (scale.astype(jnp.float32) / KV_QMAX)


def _gather_pages(cache, tables):
    """Dense page gather with dequantize-on-gather for int8 pools:
    returns [*tables.shape, kv_heads, page, d] — fp32 when quantized,
    the pool dtype otherwise."""
    if isinstance(cache, QuantizedKVPool):
        pages = cache.data[tables].astype(jnp.float32)
        s = cache.scale[tables]                       # [..., kv_heads]
        return pages * (s[..., None, None] / KV_QMAX)
    return cache[tables]


# ---------------------------------------------------------------------------
# XLA reference (tests + CPU fallback)
# ---------------------------------------------------------------------------

def paged_decode_reference(q, k_cache, v_cache, block_tables, context_lens,
                           scale=None):
    """Dense-gather paged decode: q [b, hq, d] -> out [b, hq, d]."""
    b, hq, d = q.shape
    n_pages, hkv, page, _ = k_cache.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    max_pages = block_tables.shape[1]
    safe_tables = jnp.maximum(block_tables, 0)
    # [b, max_pages, hkv, page, d] -> [b, hkv, L, d]
    kg = jnp.swapaxes(_gather_pages(k_cache, safe_tables),
                      2, 3).reshape(b, max_pages * page, hkv, d)
    vg = jnp.swapaxes(_gather_pages(v_cache, safe_tables),
                      2, 3).reshape(b, max_pages * page, hkv, d)
    kg = jnp.swapaxes(kg, 1, 2)
    vg = jnp.swapaxes(vg, 1, 2)
    qf = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhld->bhgl", qf, kg.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)[None, None, None, :]
    s = jnp.where(pos < context_lens[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgl,bhld->bhgd", p, vg.astype(jnp.float32))
    # zero-length rows (freed/parked slots) return zeros, not garbage
    out = jnp.where(context_lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas decode kernel
# ---------------------------------------------------------------------------

def _paged_decode_kernel(lens_ref, tables_ref, buf_idx, init_ref,
                         q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, acc_ref, m_ref, l_ref,
                         sem, *, page, G, max_pages, scale, group, hkv, batch):
    bi, hi, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    chunk_tokens = page * G
    ctx = lens_ref[bi]
    # every (b, h) processes AT LEAST one chunk even at length 0 — otherwise a
    # zero-length row would break the prefetch chain and the next valid row
    # would wait on semaphores armed with the wrong pages (its own output is
    # forced to zeros at the final-store below; neighbors must stay correct)
    n_chunks_b = jnp.maximum((ctx + chunk_tokens - 1) // chunk_tokens, 1)

    def chunk_copies(slot, b2, h2, c2):
        out = []
        for g in range(G):
            pidx = jnp.maximum(tables_ref[b2 * max_pages + c2 * G + g], 0)
            out.append(pltpu.make_async_copy(
                k_hbm.at[pidx, h2], k_buf.at[slot, g], sem.at[slot, 0]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[pidx, h2], v_buf.at[slot, g], sem.at[slot, 1]))
        return out

    def next_step(b2, h2, c2):
        # lexicographic next VALID step in (b, h, chunk) grid order —
        # chunks beyond a sequence's length are skipped by everyone
        # (min 1 chunk per (b, h): matches n_chunks_b above)
        nb = jnp.maximum((lens_ref[b2] + chunk_tokens - 1) // chunk_tokens, 1)
        c3 = c2 + 1
        roll_h = c3 >= nb
        h3 = jnp.where(roll_h, h2 + 1, h2)
        c3 = jnp.where(roll_h, 0, c3)
        roll_b = h3 >= hkv
        b3 = jnp.where(roll_b, b2 + 1, b2)
        h3 = jnp.where(roll_b, 0, h3)
        return b3, h3, c3

    @pl.when(ci < n_chunks_b)
    def _():
        # very first valid step of the whole grid: no one prefetched for us
        # (init flag arrives as a scalar-prefetch input set to 1 by the caller
        # and is cleared here — SMEM scratch is NOT zero-initialized)
        @pl.when(init_ref[0] == 1)
        def _():
            init_ref[0] = 0
            buf_idx[0] = 0
            for c in chunk_copies(0, bi, hi, ci):
                c.start()

        cur = buf_idx[0]
        b3, h3, c3 = next_step(bi, hi, ci)

        @pl.when(b3 < batch)
        def _():
            for c in chunk_copies(1 - cur, b3, h3, c3):
                c.start()
        for c in chunk_copies(cur, bi, hi, ci):
            c.wait()
        buf_idx[0] = 1 - cur

        @pl.when(ci == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        d = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [group, d]
        kb = k_buf[cur].reshape(chunk_tokens, d).astype(jnp.float32)
        vb = v_buf[cur].reshape(chunk_tokens, d).astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [group, CT]
        pos = ci * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (group, chunk_tokens), 1)
        s = jnp.where(pos < ctx, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # [group, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(ci == n_chunks_b - 1)
        def _():
            l_fin = l_ref[:, :1]
            l_safe = jnp.where(l_fin > 0, l_fin, 1.0)
            out = acc_ref[...] / l_safe
            # zero-length rows (freed/parked slots) emit zeros, not garbage —
            # callers may rely on inactive rows being inert
            o_ref[0, 0] = jnp.where(ctx > 0, out, 0.0).astype(o_ref.dtype)


def paged_decode_attention(q, k_cache, v_cache, block_tables, context_lens,
                           scale=None, pages_per_chunk: int = 4,
                           interpret: bool = False):
    """One-token-per-sequence paged decode.

    q: [batch, q_heads, head_dim]; caches [num_pages, kv_heads, page, d];
    block_tables [batch, max_pages_per_seq] int32; context_lens [batch] int32
    (number of valid cache tokens INCLUDING the current position's k/v, which
    must already be appended via append_paged_kv; rows with length 0 return
    ZEROS — freed/parked serving slots are guaranteed inert). Returns
    [batch, hq, d].
    """
    b, hq, d = q.shape
    n_pages, hkv, page, _ = k_cache.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    if isinstance(k_cache, QuantizedKVPool):
        # int8 block format: the Pallas kernel does not carry the
        # per-block dequant yet — route to the dense-gather reference,
        # which dequantizes in the gather (open TPU-kernel work)
        return paged_decode_reference(q, k_cache, v_cache, block_tables,
                                      context_lens, scale)
    # Mosaic page-DMA slicing needs a 128-aligned trailing dim and a
    # sublane-aligned page dim — 8 sublanes at 4-byte, 16 at 2-byte, 32 at
    # 1-byte (int8 KV cache); other shapes take the dense-gather fallback
    sublane = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(k_cache.dtype).itemsize, 8)
    shapes_ok = d % 128 == 0 and page % sublane == 0
    if not interpret and (jax.default_backend() != "tpu" or not shapes_ok):
        return paged_decode_reference(q, k_cache, v_cache, block_tables,
                                      context_lens, scale)
    max_pages = block_tables.shape[1]
    G = pages_per_chunk
    while max_pages % G:
        G -= 1
    n_chunks = max_pages // G
    # single-chunk rows have nothing to stream: the kernel's serial per-(b,h)
    # DMA chain is pure latency (~measured 3 ms in-situ at b8·h16·2 pages vs
    # ~µs for the XLA gather+einsum), so short-context serving routes to the
    # dense-gather path; the kernel wins once chunks per row >= 2
    if n_chunks < 2 and not interpret:
        return paged_decode_reference(q, k_cache, v_cache, block_tables,
                                      context_lens, scale)
    qr = q.reshape(b, hkv, group, d)

    kernel = functools.partial(
        _paged_decode_kernel, page=page, G=G, max_pages=max_pages,
        scale=float(scale), group=group, hkv=hkv, batch=b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda bi, hi, ci, *_: (bi, hi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda bi, hi, ci, *_: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, G, page, d), k_cache.dtype),
            pltpu.VMEM((2, G, page, d), v_cache.dtype),
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    # the kernel's name reaches the HLO instruction and the scope its name
    # stack: traces find the kernel by name, not by a shape
    with jax.named_scope("pt_paged_decode"):
        out = pl.pallas_call(
            kernel,
            name="pt_paged_decode",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
            # all three dims "arbitrary": the double-buffer prefetch chain carries
            # SMEM/semaphore state ACROSS batch boundaries, so no grid dim may be
            # split across megacores
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(context_lens, block_tables.reshape(-1),
          jnp.zeros((1,), jnp.int32),   # buffer index
          jnp.ones((1,), jnp.int32),    # init flag
          qr, k_cache, v_cache)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# chunk prefill over cached history (prefix cache / chunked-prefill path)
# ---------------------------------------------------------------------------

def paged_prefill_attention(q, k_cache, v_cache, block_tables, chunk_starts,
                            scale=None):
    """Attention for a prefill CHUNK whose rows sit at per-row absolute
    offsets inside already-partially-filled paged caches.

    q: [b, s, hq, d] — queries for tokens at absolute positions
    ``chunk_starts[b] + i`` (i in [0, s)); the chunk's own k/v must already
    be appended into the pages (append-then-gather, so within-chunk keys and
    the cached prefix are read through ONE code path). Returns [b, s, hq, d].

    Keys are gathered densely from the block table (full ``max_pages*page``
    extent) and masked by absolute position: query at position p attends
    keys at positions <= p. The mask depends only on ABSOLUTE positions and
    the gathered extent is fixed per engine, so GIVEN the same cached k/v
    bytes a token's output is bit-identical no matter how the prompt is
    chunked or how much of it came from the prefix cache — the property the
    serving engine's warm==cold token-equality guarantee rests on (the
    engine's module docstring scopes what "same bytes" means at re-stepped
    block-final positions). Rows are independent, so several rows may SHARE
    one sequence's block table at different ``chunk_starts`` — the fused
    engine's prompt-packing prefill flattens (slot, chunk) pairs into the
    rows of one call; because every row's k/v is appended before any row's
    gather, a later chunk reads an earlier chunk's pages written in the
    same program, bit-identical to sequential chunk calls.
    Stays an XLA gather+einsum (no Pallas
    kernel): prefill is projection/MLP-bound at serving chunk sizes and this
    runs once per admitted chunk, unlike the per-token decode kernel."""
    b, s, hq, d = q.shape
    n_pages, hkv, page, _ = k_cache.shape
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    max_pages = block_tables.shape[1]
    L = max_pages * page
    safe_tables = jnp.maximum(block_tables, 0)
    kg = jnp.swapaxes(_gather_pages(k_cache, safe_tables),
                      2, 3).reshape(b, L, hkv, d)
    vg = jnp.swapaxes(_gather_pages(v_cache, safe_tables),
                      2, 3).reshape(b, L, hkv, d)
    kg = jnp.swapaxes(kg, 1, 2).astype(jnp.float32)      # [b, hkv, L, d]
    vg = jnp.swapaxes(vg, 1, 2).astype(jnp.float32)
    qf = q.reshape(b, s, hkv, group, d).astype(jnp.float32)
    qf = jnp.transpose(qf, (0, 2, 3, 1, 4))              # [b, hkv, g, s, d]
    sc = jnp.einsum("bhgsd,bhld->bhgsl", qf, kg) * scale
    q_pos = chunk_starts[:, None] + jnp.arange(s)        # [b, s] absolute
    keep = (jnp.arange(L)[None, None, :]
            <= q_pos[:, :, None])                        # [b, s, L]
    sc = jnp.where(keep[:, None, None, :, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgsl,bhld->bhgsd", p, vg)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s, hq, d)
    return out.astype(q.dtype)


def paged_verify_attention(q, k_cache, v_cache, block_tables, row_starts,
                           scale=None):
    """Speculative-decode VERIFY attention: score a K+1-token draft window
    per row in ONE pass (inference/serving.py speculative mega-step).

    q: [b, s, hq, d] — queries for the window [last_token, draft_1..draft_K]
    whose rows sit at per-row absolute offsets ``row_starts[b] + i`` inside
    already-partially-filled paged caches. The window's own k/v must
    already be appended (append-then-gather), exactly the
    :func:`paged_prefill_attention` machinery — which is what this
    delegates to: the absolute-position mask means window position i
    attends the cached prefix plus drafts 0..i, so the logits at position
    i are IDENTICAL (same gather extent, same masked softmax) to what a
    sequential ``paged_token_step`` at that position would compute given
    the same cache bytes — the greedy byte-identity guarantee of
    speculative decoding rests here. Rejected drafts' appended k/v needs
    no explicit rollback: positions past the accepted prefix sit beyond
    the advanced context length, are never attended, and are overwritten
    as decode proceeds (the engine's standard pad-append invariant).
    int8 pools dequantize in the gather like every other read path.

    NOTE this is a NAMED THIN DELEGATION: the production verify program
    (``paged_verify_step`` -> layer ``paged_prefill_chunk``) dispatches
    the shared :func:`paged_prefill_attention` body directly — verify and
    chunk prefill are deliberately ONE implementation, which is what the
    byte-identity argument above rests on. Behavioral changes belong in
    that shared body; changing only this wrapper changes tests, not
    serving."""
    return paged_prefill_attention(q, k_cache, v_cache, block_tables,
                                   row_starts, scale)


def copy_pages(k_cache, v_cache, src, dst):
    """Copy page(s) ``src`` -> ``dst`` across a (k, v) pool pair — the
    copy-on-write primitive for shared prefix blocks. Traced-index
    friendly: one compiled program serves every (src, dst). Accepts a
    scalar pair (the legacy per-admission COW) or equal-length index
    vectors (the fused engine batches a whole admission wave's COW copies
    into one dispatch, padding with park->park self-copies — duplicate
    destinations among the pads write identical bytes, so the scatter
    stays deterministic). int8 pools copy the per-block scales alongside
    the page bytes — a COW copy must carry the whole block format, or the
    private copy would dequantize under the wrong scale."""
    src = jnp.atleast_1d(jnp.asarray(src, jnp.int32))
    dst = jnp.atleast_1d(jnp.asarray(dst, jnp.int32))
    if isinstance(k_cache, QuantizedKVPool):
        return (QuantizedKVPool(k_cache.data.at[dst].set(k_cache.data[src]),
                                k_cache.scale.at[dst].set(k_cache.scale[src])),
                QuantizedKVPool(v_cache.data.at[dst].set(v_cache.data[src]),
                                v_cache.scale.at[dst].set(v_cache.scale[src])))
    k_cache = k_cache.at[dst].set(k_cache[src])
    v_cache = v_cache.at[dst].set(v_cache[src])
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# refcounted block allocator + radix prefix cache (host-side bookkeeping)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted allocator over the paged-KV pool's page ids.

    Page states: FREE (in the free list), ACTIVE (refcount >= 1 — mapped
    into at least one request's block table), CACHED-IDLE (refcount == 0 but
    still registered in a :class:`RadixPrefixCache` — its KV content is
    retained for future prefix hits and reclaimed lazily via LRU eviction),
    or HELD (fault-drill resource exhaustion, ``hold()``).

    Refcounts count REQUEST references only: ``alloc`` hands out fresh
    blocks at refcount 1, every additional request sharing a block calls
    ``incref``, and ``decref`` at request completion/eviction returns the
    block to the free list ONLY when nothing else references it and no
    prefix cache retains it — freeing a block another request still reads
    is the corruption class the serving fault drill exercises."""

    def __init__(self, num_blocks: int):
        self.num_blocks = int(num_blocks)
        self._free = collections.deque(range(self.num_blocks))
        self._ref: Dict[int, int] = {}
        self._held: List[int] = []
        # wired by the owner after constructing the radix cache:
        # is_cached(block) -> bool keeps refcount-0 blocks out of the free
        # list while a prefix cache still maps them
        self.is_cached: Callable[[int], bool] = lambda b: False

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def hold(self, n: int) -> int:
        """Remove up to ``n`` free blocks from circulation (fault injection:
        seeded pool exhaustion). Returns how many were actually held."""
        took = 0
        while took < n and self._free:
            self._held.append(self._free.popleft())
            took += 1
        return took

    def release_held(self) -> int:
        n = len(self._held)
        self._free.extend(self._held)
        self._held.clear()
        return n

    def alloc(self, n: int,
              evict: Optional[Callable[[int], int]] = None,
              ) -> Optional[List[int]]:
        """Allocate ``n`` blocks at refcount 1. When the free list is short,
        ``evict(shortfall)`` (the radix cache's LRU reclaimer) may free
        cached-idle blocks first. Returns None when the pool genuinely
        cannot satisfy the request — callers defer/backpressure, they never
        overcommit."""
        if n <= 0:
            return []
        if len(self._free) < n and evict is not None:
            evict(n - len(self._free))
        if len(self._free) < n:
            return None
        out = [self._free.popleft() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            rc = self._ref.get(b, 0)
            if rc == 0 and not self.is_cached(b):
                raise RuntimeError(
                    f"incref of free block {b} — a prefix-cache hit mapped "
                    "a block the allocator does not consider live")
            # rc == 0 with is_cached: a CACHED-IDLE block coming back into
            # active service on a prefix hit
            self._ref[b] = rc + 1

    def decref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            rc = self._ref.get(b, 0)
            if rc <= 0:
                raise RuntimeError(f"decref of free block {b} (double free)")
            if rc == 1:
                del self._ref[b]
                if not self.is_cached(b):
                    self._free.append(b)
            else:
                self._ref[b] = rc - 1

    def free_cached(self, block: int) -> None:
        """Return a CACHED-IDLE block to the free list — only the radix
        cache's eviction path may call this, after unregistering it."""
        if self._ref.get(block, 0):
            raise RuntimeError(
                f"evicting block {block} with refcount "
                f"{self._ref[block]} — still mapped by a live request")
        self._free.append(block)


class _RadixNode:
    __slots__ = ("children", "block", "parent", "key", "last_used")

    def __init__(self, parent=None, key=None, block=None):
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.key = key
        self.block = block
        self.last_used = 0


class RadixPrefixCache:
    """Radix/trie over page-sized prompt-token chunks -> filled KV blocks.

    Each node maps ONE full block of ``page_size`` prompt tokens to the page
    holding that block's k/v (page ids are shared by every layer's pool, so
    one id is the whole transformer's prefix block). ``match`` walks the
    longest fully-cached prefix; ``insert`` registers a request's freshly
    prefilled full prompt blocks (first writer wins — a duplicate chain from
    a same-wave miss simply stays private to its request). Eviction is LRU
    over leaf nodes whose blocks have refcount 0, cascading upward, so a
    cached chain is never broken in the middle."""

    def __init__(self, page_size: int, allocator: BlockAllocator):
        self.page_size = int(page_size)
        self.allocator = allocator
        self.root = _RadixNode()
        self._by_block: Dict[int, _RadixNode] = {}
        self._tick = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        allocator.is_cached = self.has_block

    def __len__(self) -> int:
        return len(self._by_block)

    def has_block(self, block: int) -> bool:
        return block in self._by_block

    def _chunks(self, tokens) -> List[tuple]:
        p = self.page_size
        n = len(tokens) // p
        return [tuple(int(t) for t in tokens[i * p:(i + 1) * p])
                for i in range(n)]

    def match(self, tokens) -> List[int]:
        """Longest-prefix match over FULL blocks; returns the cached block
        ids in order (possibly empty). Bumps LRU recency along the path;
        the caller increfs before mapping them into a table."""
        self._tick += 1
        node = self.root
        out: List[int] = []
        for key in self._chunks(tokens):
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._tick
            out.append(child.block)
            node = child
        if out:
            self.hits += 1
        else:
            self.misses += 1
        return out

    def insert(self, tokens, blocks: Sequence[int]) -> List[int]:
        """Register ``blocks[i]`` as the cache entry for the i-th full block
        of ``tokens``. Existing nodes keep their block (the duplicate stays
        private to the inserting request). Returns the block ids newly
        registered."""
        self._tick += 1
        node = self.root
        registered: List[int] = []
        for key, block in zip(self._chunks(tokens), blocks):
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(parent=node, key=key, block=int(block))
                node.children[key] = child
                self._by_block[child.block] = child
                registered.append(child.block)
            child.last_used = self._tick
            node = child
        return registered

    def evict_lru(self, n: int) -> int:
        """Evict up to ``n`` blocks — LRU over refcount-0 LEAVES, cascading
        to parents as they become leaves. Returns how many blocks went back
        to the free list."""
        freed = 0
        while freed < n:
            victims = [nd for nd in self._by_block.values()
                       if not nd.children
                       and self.allocator.refcount(nd.block) == 0]
            if not victims:
                break
            victim = min(victims, key=lambda nd: nd.last_used)
            victim.parent.children.pop(victim.key)
            del self._by_block[victim.block]
            self.allocator.free_cached(victim.block)
            self.evictions += 1
            freed += 1
        return freed


# ---------------------------------------------------------------------------
# cache maintenance (XLA scatters — bandwidth-bound, no kernel needed)
# ---------------------------------------------------------------------------

@functools.partial(jax.named_call, name="pt.kv_write")
def append_paged_kv(k_cache, v_cache, k_new, v_new, block_tables, positions,
                    seq_ids=None):
    """Scatter new tokens into the page pool.

    k_new/v_new: [n_tokens, kv_heads, d]; positions [n_tokens] absolute
    position of each token within its sequence; seq_ids [n_tokens] row of
    block_tables per token (defaults to arange — one token per sequence,
    the decode step). Returns updated (k_cache, v_cache)."""
    n_tokens = k_new.shape[0]
    page = k_cache.shape[2]
    if seq_ids is None:
        seq_ids = jnp.arange(n_tokens, dtype=jnp.int32)
    page_idx = block_tables[seq_ids, positions // page]      # [n]
    offs = positions % page                                   # [n]
    if isinstance(k_cache, QuantizedKVPool):
        return (_append_quantized(k_cache, k_new, page_idx, offs),
                _append_quantized(v_cache, v_new, page_idx, offs))
    k_cache = k_cache.at[page_idx, :, offs, :].set(k_new)
    v_cache = v_cache.at[page_idx, :, offs, :].set(v_new)
    return k_cache, v_cache


def _append_quantized(pool: QuantizedKVPool, x_new, page_idx, offs):
    """Quantize-on-append into the int8 block format (one pool side).

    1. Scatter-MAX the per-(page, head) scales with the incoming tokens'
       absmax — correct under duplicate page indices (several tokens of a
       prefill chunk landing in one page), unlike a gather/rewrite.
    2. REquantize already-stored values of grown blocks: one elementwise
       pass over the pool at ratio old_scale/new_scale — the ratio is
       exactly 1.0 everywhere a block did not grow, and round(q * 1.0)
       reproduces q bit-for-bit for every int8 value, so untouched blocks
       are byte-stable. (XLA fuses this into a single pool pass; pushing
       the rescale into a page-local kernel is the open TPU-side work.)
    3. Write the new tokens quantized under the grown scale — the same
       scatter shape as the fp path, so duplicate semantics (parking-page
       dummies) are unchanged.
    """
    s_tok = kv_absmax(x_new)                                  # [n, h]
    new_scale = pool.scale.at[page_idx].max(s_tok)            # [P, h]
    ratio = jnp.where(new_scale > 0,
                      pool.scale / jnp.where(new_scale > 0, new_scale, 1.0),
                      1.0)
    data = jnp.clip(jnp.round(pool.data.astype(jnp.float32)
                              * ratio[:, :, None, None]),
                    -KV_QMAX, KV_QMAX)
    q_new = quantize_kv(x_new, new_scale[page_idx][:, :, None])
    data = data.astype(jnp.int8).at[page_idx, :, offs, :].set(q_new)
    return QuantizedKVPool(data, new_scale)


def gather_chain_pages(kv, blocks):
    """Host-materialize a block chain's page bytes from every layer's
    (k, v) pool pair — the EXPORT half of KV-block migration
    (inference/disagg.py): ``kv`` is the engine's per-layer
    ``[(k_pages, v_pages), ...]`` list, ``blocks`` the chain's page ids in
    block-table order. Returns ``[(k_np, v_np), ...]`` with arrays of
    shape ``[len(blocks), kv_heads, page, head_dim]``. The np.asarray
    readback fences any in-flight append/decode program that wrote these
    pages, so the bytes are exactly what the next decode step would have
    attended. int8 pools export their RAW int8 page bytes (the dequant
    scales travel separately — :func:`gather_chain_scales`), so the wire
    artifact's crc covers the quantized bytes exactly as stored."""
    import numpy as np

    idx = np.asarray(blocks, np.int32)
    out = []
    for k, v in kv:
        if isinstance(k, QuantizedKVPool):
            out.append((np.asarray(k.data[idx]), np.asarray(v.data[idx])))
        else:
            out.append((np.asarray(k[idx]), np.asarray(v[idx])))
    return out


def gather_chain_scales(kv, blocks):
    """Per-layer (k_scales, v_scales) host arrays for a chain's blocks
    ([len(blocks), kv_heads] f32 each) — the scale half of an int8 chain
    export. Returns None for fp pools (no scales in the block format)."""
    import numpy as np

    if not kv or not isinstance(kv[0][0], QuantizedKVPool):
        return None
    idx = np.asarray(blocks, np.int32)
    return [(np.asarray(k.scale[idx]), np.asarray(v.scale[idx]))
            for k, v in kv]


def scatter_chain_pages(kv, blocks, pages, scales=None):
    """Write exported chain bytes into freshly-allocated pool pages — the
    IMPORT half of KV-block migration. ``pages`` is
    :func:`gather_chain_pages` output (host arrays); each layer's pool
    takes one eager scatter (control-plane dispatch — migration happens
    once per request, never on the decode hot path). int8 pools take the
    per-block ``scales`` (from :func:`gather_chain_scales` or the PTKV1
    header) alongside the raw int8 bytes. Returns the updated per-layer
    ``[(k_pages, v_pages), ...]`` list."""
    idx = jnp.asarray(blocks, jnp.int32)
    out = []
    for li, ((k, v), (pk, pv)) in enumerate(zip(kv, pages)):
        if isinstance(k, QuantizedKVPool):
            if scales is None:
                raise ValueError("int8 pool import needs per-block scales")
            ks, vs = scales[li]
            out.append((
                QuantizedKVPool(
                    k.data.at[idx].set(jnp.asarray(pk, jnp.int8)),
                    k.scale.at[idx].set(jnp.asarray(ks, jnp.float32))),
                QuantizedKVPool(
                    v.data.at[idx].set(jnp.asarray(pv, jnp.int8)),
                    v.scale.at[idx].set(jnp.asarray(vs, jnp.float32)))))
        else:
            out.append((k.at[idx].set(jnp.asarray(pk, k.dtype)),
                        v.at[idx].set(jnp.asarray(pv, v.dtype))))
    return out


def gather_paged_kv(k_cache, v_cache, block_tables, max_len):
    """Dense [b, max_len, hkv, d] views of the paged cache (prefill path /
    debugging; int8 pools come back dequantized fp32). max_len must be a
    multiple of page size."""
    b = block_tables.shape[0]
    page = k_cache.shape[2]
    hkv, d = k_cache.shape[1], k_cache.shape[3]
    n = max_len // page
    tables = jnp.maximum(block_tables[:, :n], 0)
    kg = jnp.swapaxes(_gather_pages(k_cache, tables),
                      2, 3).reshape(b, max_len, hkv, d)
    vg = jnp.swapaxes(_gather_pages(v_cache, tables),
                      2, 3).reshape(b, max_len, hkv, d)
    return kg, vg
