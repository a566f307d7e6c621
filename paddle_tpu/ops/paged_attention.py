"""Paged (block) KV-cache attention — Pallas TPU kernels for batched serving.

TPU-native replacement for the reference's paged serving kernels
(/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
python surface python/paddle/incubate/nn/functional/block_multihead_attention.py):
KV lives in a pool of fixed-size pages; each sequence owns a list of pages via a
block table, so cache memory is bounded by total tokens, not batch × max_len.

Layouts (reference block_multihead_attention):
  k_cache/v_cache: [num_pages, kv_heads, page_size, head_dim], the logical
                   form; or lane-dense, [num_pages, kv_heads // f, page_size,
                   128] with f = 128 // head_dim heads side by side in a row
                   (``kv_pool_shape``: where head_dim divides the 128 lanes
                   and f divides the heads; the same bytes, one tile a page
                   and head group). A call reads the form off its own shapes:
                   the pool's trailing width against the query's head_dim.
  block_tables:    [batch, pages_per_seq] int32 (-1 = unassigned)
  context_lens:    [batch] int32 — tokens already in cache (incl. current step)

Decode kernel design (v5e, PERF.md section 6, PR 25: 105 us a call at the
chat-batch cell's shapes, 80% of the time its bytes take at 819 GB/s):
  - the unit of work is (row, chunk of pages) for ALL KV heads. The pool's
    layout makes a page's [kv_heads, page, d] one contiguous block, so one DMA
    a page and pool side brings it into a VMEM buffer laid out
    [kv_heads, chunk_tokens, d]: every head's chunk is one tile.
  - grid (batch,); inside a step a loop walks the row's chunks and carries
    the online softmax in float32. While a chunk computes, the next one is
    in flight in the other buffer slot: the row's next chunk, or after its
    last one the next row's first, so the chain runs across rows. An SMEM
    word carries the slot from row to row.
  - context lengths and block tables arrive via scalar prefetch. Only pages
    that hold context are fetched (a row's last chunk is cut at its last
    page); rows of length 0 fetch and compute nothing.
  - the chunk's size follows from the shapes (`_decode_chunk_pages`): page
    size, KV heads, head_dim, the pool's dtype, pages a row, a VMEM budget.
  - all heads of a chunk are two batched dots, `q.K^T` and `p.V`, with
    `group` query rows a KV head.

Chunk kernel design (`pt_paged_chunk`, the packed prefill chunk's attention;
PERF.md section 6, PR 42): the decode kernel's walk with `s` queries a row.
  - grid (rows,); a row's query block is `[kv_heads, group * s, d]`, its
    walk the pages from that of `start - window + 1` (0 on a full layer) to
    that of its last query, fetched a block ahead into the same double
    buffer with the chain carried from row to row. Nothing past a row's
    last query or behind its window is fetched.
  - key blocks are aligned to ABSOLUTE positions, block `j` holding
    `[j * CT, (j + 1) * CT)`, and a block that is wholly masked for a query
    leaves its running maximum, sum and accumulator bit for bit alone: a
    query meets the same blocks in the same order however its prompt was
    chunked (the warm == cold guarantee, `paged_prefill_attention`).
  - the KV heads are looped inside a block; the running maximum, sum and
    accumulator of every query row live in VMEM scratch; a block every
    query of the row sees whole takes a path without the mask's passes.
"""

from __future__ import annotations

import collections
import functools
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
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
    equal memory. The Pallas kernels do not yet carry the dequant (int8
    routes to the XLA reference paths — open TPU-kernel work)."""

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


# ---------------------------------------------------------------------------
# state that is not pages of K and V (docs/SERVING.md "State that is not pages")
# ---------------------------------------------------------------------------

class LayerStateError(TypeError):
    """PT-SRV-009: an operation that moves or reinterprets a layer's cache
    met a layer kind it cannot carry (names the kind). Raised instead of
    dropping the state: a stream that silently lost a layer's state is the
    one outcome the serving contract forbids."""


@jax.tree_util.register_pytree_node_class
class PageState:
    """A layer's fixed-size state, kept WITH the pages of the pool.

    Some layer kinds keep no K and V a token but a fixed block a sequence:
    a short causal convolution needs its last ``slots - 1`` inputs, whatever
    the sequence's length. ``ring`` [num_pages, slots, width] holds, for
    every page of the pool, the layer's input at the last ``slots``
    positions written in that page: position ``p`` lives in page
    ``tables[row, p // page]`` at ring slot ``p % slots``. A sequence's
    running state is the ring of its current page (and, for the first
    ``slots - 1`` offsets of a page, of the page before); a cached page's
    ring IS the snapshot a prefix hit resumes from. So the state is
    allocated, shared, copied on write, evicted, stolen and migrated with
    the page, by the same table rows, and no program ever restores it.

    ``page`` (tokens a page) is static. Registered as a pytree, so it rides
    jit carries and donation like the (k, v) pairs beside it in
    ``caches["kv"]``."""

    __slots__ = ("ring", "page")

    def __init__(self, ring, page: int):
        self.ring = ring
        self.page = int(page)

    def tree_flatten(self):
        return (self.ring,), self.page

    @classmethod
    def tree_unflatten(cls, page, children):
        return cls(children[0], page)

    @property
    def slots(self) -> int:
        return self.ring.shape[1]

    @property
    def nbytes(self) -> int:
        return int(self.ring.size) * jnp.dtype(self.ring.dtype).itemsize

    def __repr__(self):
        return (f"PageState(ring={tuple(self.ring.shape)}, "
                f"dtype={self.ring.dtype}, page={self.page})")


@functools.partial(jax.named_call, name="pt.state_write")
def page_state_write(state: PageState, values, block_tables, positions,
                     seq_ids=None, valid=None):
    """Write ``values`` [n, width] (the layer's inputs at absolute
    ``positions`` [n] of rows ``seq_ids``, default one a row) into the rings
    of the pages that hold those positions. ``valid`` [n] bool drops
    entries (the zero-padded tail of a last prefill chunk): they must leave
    no trace. Among the entries of one call that share a (page, slot) the
    caller keeps the last only (``page_state_keep_last``): a scatter with
    duplicate indices has no order."""
    n = values.shape[0]
    if seq_ids is None:
        seq_ids = jnp.arange(n, dtype=jnp.int32)
    page_idx = block_tables[seq_ids, positions // state.page]
    if valid is not None:
        # out of range: jax drops the update
        page_idx = jnp.where(valid, page_idx, state.ring.shape[0])
    ring = state.ring.at[page_idx, positions % state.slots].set(
        values.astype(state.ring.dtype), mode="drop")
    return PageState(ring, state.page)


def page_state_keep_last(valid, offsets, slots: int, page: int):
    """For a chunk's rows ([b, s] ``valid`` and in-page ``offsets``): which
    positions are the last valid writer of their (page, ring slot): the one
    ``slots`` further on lies in another page or is not valid."""
    later = jnp.pad(valid[:, slots:], ((0, 0), (0, slots)))
    return valid & ~(later & (offsets + slots < page))


def page_state_read(state: PageState, block_tables, positions, back: int,
                    seq_ids=None):
    """The layer's inputs at ``positions - back .. positions - 1``:
    [n, back, width], oldest first, zeros before the sequence's start."""
    n = positions.shape[0]
    if seq_ids is None:
        seq_ids = jnp.arange(n, dtype=jnp.int32)
    q = positions[:, None] - jnp.arange(back, 0, -1, dtype=jnp.int32)
    qc = jnp.maximum(q, 0)
    page_idx = block_tables[seq_ids[:, None], qc // state.page]
    vals = state.ring[page_idx, qc % state.slots]
    return jnp.where((q >= 0)[..., None], vals, jnp.zeros((), vals.dtype))


@jax.tree_util.register_pytree_node_class
class SeqState:
    """A layer's state kept a SEQUENCE, not with the pages: what is too
    large to ride them and is no function of a few last inputs (a Mamba-2
    layer: ``ssm`` [slots, heads, head_dim, state] float32, the recurrence's
    matrix, and ``conv`` [slots, taps - 1, width], the inputs its short
    convolution still needs). Row ``i`` belongs to the engine's slot ``i``
    for as long as a request holds the slot. Nothing resets it: a packed
    chunk row that starts at position 0, and a token step at position 0,
    start from zero; a later chunk row and a decode step resume from the
    slot's row; rows that do not decode, dummy rows and padded positions
    leave it as it was; when the request leaves, the row is dead until the
    next one starts from zero. No page carries it, so it cannot be shared by
    a prefix hit, exported with a chain or moved to another slot: what would
    need that raises ``LayerStateError`` (docs/SERVING.md "State that is not
    pages"). Registered as a pytree: it rides jit carries and donation
    inside ``caches["kv"]`` like the entries beside it."""

    __slots__ = ("ssm", "conv")

    def __init__(self, ssm, conv):
        self.ssm = ssm
        self.conv = conv

    def tree_flatten(self):
        return (self.ssm, self.conv), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    @property
    def nbytes(self) -> int:
        return sum(int(a.size) * jnp.dtype(a.dtype).itemsize
                   for a in (self.ssm, self.conv))

    def __repr__(self):
        return (f"SeqState(ssm={tuple(self.ssm.shape)} {self.ssm.dtype}, "
                f"conv={tuple(self.conv.shape)} {self.conv.dtype})")


def _kind(entry) -> str:
    return ("state" if isinstance(entry, PageState) else
            "seq" if isinstance(entry, SeqState) else "kv")


def layer_kinds(kv) -> List[str]:
    """What each layer of ``caches["kv"]`` keeps: "kv" (pages of K and V a
    token), "state" (a fixed block kept with the page, ``PageState``) or
    "seq" (a state kept a sequence, by slot: ``SeqState``)."""
    return [_kind(e) for e in kv]


def pool_num_pages(kv) -> int:
    """Pages in the pool, by the first layer that keeps pages."""
    e = next(e for e in kv if not isinstance(e, SeqState))
    return int((e.ring if isinstance(e, PageState) else e[0]).shape[0])


def pool_geometry(kv) -> List[tuple]:
    """Per layer ``(kind, shape of a page's block as stored, dtype)``: two
    engines can exchange pages only where these agree (a lane-dense block
    reads ``(kv_heads // f, page, 128)``: engines of one model make one
    form, ``kv_pool_shape``)."""
    out = []
    for e in kv:
        a = (e.ring if isinstance(e, PageState) else
             e.ssm if isinstance(e, SeqState) else e[0])
        out.append((_kind(e), tuple(a.shape[1:]), str(a.dtype)))
    return out


def kernel_layers(kv) -> tuple:
    """``(layers whose pools pt_paged_decode reads, layers that keep K and
    V)``: whether the kernel and the in-place append engage is fixed with
    the pools' shapes, when an engine is built."""
    pools = [e[0] for e in kv if _kind(e) == "kv"]
    return sum(_kernel_takes(p) for p in pools), len(pools)


def page_append_layers(kv, chunk_tokens: int) -> int:
    """Layers that keep K and V whose packed chunk of ``chunk_tokens`` a row
    appends by the page (``append_paged_chunk``)."""
    return sum(_appends_by_page(e[0], chunk_tokens) for e in kv
               if _kind(e) == "kv")


def state_bytes(kv, kind: str = "state") -> int:
    """Bytes of the state rings kept with the pages ("state") or of the
    state kept a sequence ("seq"); 0 without such layers."""
    return sum(e.nbytes for e in kv if _kind(e) == kind)


def copy_layer_pages(entry, src, dst):
    """``copy_pages`` for one layer's cache entry of either kind: a state
    ring is copied with the page like K and V."""
    if isinstance(entry, PageState):
        src = jnp.atleast_1d(jnp.asarray(src, jnp.int32))
        dst = jnp.atleast_1d(jnp.asarray(dst, jnp.int32))
        return PageState(entry.ring.at[dst].set(entry.ring[src]), entry.page)
    return copy_pages(*entry, src, dst)


def require_kv_layers(kv, what: str):
    kinds = layer_kinds(kv)
    for kind, held in (("state", "PageState rings"),
                       ("seq", "SeqState, kept a slot")):
        if kind in kinds:
            raise LayerStateError(
                f"PT-SRV-009: {what} carries pages of K and V only; layer(s) "
                f"{[i for i, k in enumerate(kinds) if k == kind]} are of "
                f"kind {kind!r} ({held}), which it would drop")


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


_LANES = 128


def _sublanes(dtype) -> int:
    """Rows of a tile: 8 sublanes at 4-byte, 16 at 2-byte, 32 at 1-byte."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def kv_pool_shape(num_pages, kv_heads, page, head_dim, dtype,
                  shards: int = 1) -> tuple:
    """The shape in which one side (K or V) of a layer's pool is stored; who
    makes pools for an engine asks here (``_init_paged_caches``).

    Heads narrower than the 128 lanes are stored lane-dense, ``f = 128 //
    head_dim`` KV heads to a row: ``[pages, kv_heads // f, page, 128]``, row
    ``(p, j, s)`` holding heads ``j*f .. j*f+f-1`` of slot ``s`` side by
    side. The bytes are the logical form's; a page's block of one head group
    is whole tiles, which is what ``pt_paged_decode``'s page DMA needs
    (``_kernel_takes``). The rule reads shapes alone, never the backend:
    ``head_dim`` divides 128, ``f`` divides the KV heads of each of the
    ``shards`` a ``tp`` mesh cuts the pool into (axis 1: a shard reads the
    form off its local shapes like everyone), the page fills the dtype's
    sublanes. Whatever does not divide (head_dim 96 or 80, one KV head of
    64) keeps the logical ``[pages, kv_heads, page, head_dim]``, as does
    every int8 pool."""
    f = _LANES // head_dim if head_dim < _LANES else 1
    if (f > 1 and _LANES % head_dim == 0 and kv_heads % (f * shards) == 0
            and page % _sublanes(dtype) == 0):
        return (num_pages, kv_heads // f, page, _LANES)
    return (num_pages, kv_heads, page, head_dim)


def pool_pages(num_pages: int, dtype) -> int:
    """Pages a pool is made with: the count asked for, rounded up to the
    rows of the dtype's tile (``_sublanes``). The device keeps a state ring
    ``[pages, slots, width]`` slot-major (a few slots would otherwise pad to
    a tile of eight or sixteen), so its scatter collapses ``[slots, pages]``
    to rows, and that is a bitcast only where the tile divides the pages:
    at 10,497 pages of bf16 the ring was copied whole, in and out, every
    chunk and conv layer (129 MB of temporaries for a described v5e; none at
    10,512: PERF.md section 6, PR 35). The spare pages lie after the last
    one asked for; no table names them."""
    rows = _sublanes(dtype)
    return -(-int(num_pages) // rows) * rows


def _pool_fold(pool, d) -> int:
    """KV heads of width ``d`` in a row of ``pool``: 1 in the logical form
    (trailing width ``d``), ``128 // d`` lane-dense (trailing width 128)."""
    w = pool.shape[-1]
    if w == d:
        return 1
    if w != _LANES or w % d or isinstance(pool, QuantizedKVPool):
        raise ValueError(
            f"a pool of trailing width {w} holds no heads of {d}: it is "
            f"neither [pages, kv_heads, page, {d}] nor lane-dense "
            f"[pages, kv_heads // f, page, {_LANES}]")
    return w // d


def logical_page_shape(pool, head_dim) -> tuple:
    """``(kv_heads, page, head_dim)`` of a page's block in the logical order
    (the PTKV1 artifact's), for a pool in either form; a pool that holds no
    heads of ``head_dim`` gives its stored shape."""
    groups, page, w = (int(n) for n in pool.shape[1:])
    if (w != head_dim and w == _LANES and w % head_dim == 0
            and not isinstance(pool, QuantizedKVPool)):
        return (groups * (w // head_dim), page, head_dim)
    return (groups, page, w)


def fold_kv_pages(pages, f: int):
    """Logical page blocks [n, kv_heads, page, d] -> lane-dense
    [n, kv_heads // f, page, f * d] (jax or numpy; ``f`` 1: as given)."""
    if f == 1:
        return pages
    *lead, h, page, d = pages.shape
    return pages.reshape(*lead, h // f, f, page, d).swapaxes(-2, -3).reshape(
        *lead, h // f, page, f * d)


def unfold_kv_pages(pages, d: int):
    """The inverse: [..., groups, page, f * d] -> [..., groups * f, page, d]."""
    *lead, g, page, w = pages.shape
    if w == d:
        return pages
    f = w // d
    return pages.reshape(*lead, g, page, f, d).swapaxes(-2, -3).reshape(
        *lead, g * f, page, d)


def _gather_pages(cache, tables, d):
    """Dense page gather with dequantize-on-gather for int8 pools:
    returns [*tables.shape, kv_heads, page, d] — fp32 when quantized,
    the pool dtype otherwise. The ONE place where what reads a pool through
    XLA un-folds a lane-dense one (a reshape and a transpose of the gathered
    pages, never of the pool)."""
    if isinstance(cache, QuantizedKVPool):
        pages = cache.data[tables].astype(jnp.float32)
        s = cache.scale[tables]                       # [..., kv_heads]
        return pages * (s[..., None, None] / KV_QMAX)
    return unfold_kv_pages(cache[tables], d)


# ---------------------------------------------------------------------------
# XLA reference (tests + CPU fallback)
# ---------------------------------------------------------------------------

def paged_decode_reference(q, k_cache, v_cache, block_tables, context_lens,
                           scale=None, window: Optional[int] = None):
    """Dense-gather paged decode: q [b, hq, d] -> out [b, hq, d]. With a
    ``window`` the query (at position ``len - 1``) sees the last ``window``
    positions only, its own among them."""
    b, hq, d = q.shape
    page = k_cache.shape[2]
    hkv = k_cache.shape[1] * _pool_fold(k_cache, d)
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    max_pages = block_tables.shape[1]
    safe_tables = jnp.maximum(block_tables, 0)
    # [b, max_pages, hkv, page, d] -> [b, hkv, L, d]
    kg = jnp.swapaxes(_gather_pages(k_cache, safe_tables, d),
                      2, 3).reshape(b, max_pages * page, hkv, d)
    vg = jnp.swapaxes(_gather_pages(v_cache, safe_tables, d),
                      2, 3).reshape(b, max_pages * page, hkv, d)
    kg = jnp.swapaxes(kg, 1, 2)
    vg = jnp.swapaxes(vg, 1, 2)
    qf = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhld->bhgl", qf, kg.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page)[None, None, None, :]
    keep = pos < context_lens[:, None, None, None]
    if window is not None:
        keep &= pos >= context_lens[:, None, None, None] - window
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgl,bhld->bhgd", p, vg.astype(jnp.float32))
    # zero-length rows (freed/parked slots) return zeros, not garbage
    out = jnp.where(context_lens[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas decode kernel
# ---------------------------------------------------------------------------

#: VMEM the decode kernel spends on page buffers (K and V, two slots each),
#: and the longest chunk of context it computes at once
_DECODE_VMEM_BUDGET = 4 << 20
_DECODE_MAX_CHUNK_TOKENS = 512


def _decode_chunk_pages(max_pages, hkv, page, d, itemsize,
                        max_tokens=_DECODE_MAX_CHUNK_TOKENS):
    """Pages in one unit of the decode kernel's work, from what the call can
    see: as many as fit the VMEM budget (every page brings all its KV heads,
    for K and for V, double buffered), no more than
    ``_DECODE_MAX_CHUNK_TOKENS`` of context, no more than a row's table.
    On the v5e at the chat-batch shapes (8 KV heads, page 16, head_dim 128,
    bf16) chunks of 128 / 256 / 512 tokens ran 121 / 107 / 105 us a call."""
    page_bytes = 4 * hkv * page * d * itemsize
    fit = min(_DECODE_VMEM_BUDGET // page_bytes,
              max_tokens // page, max_pages)
    return max(fit, 1)


def _page_copies(k_hbm, v_hbm, k_buf, v_buf, sem, slot, pidx, g):
    """The two DMAs of page ``pidx``, [kv_heads, page, d] each, into place
    ``g`` of buffer slot ``slot``: K and V on the slot's two semaphores."""
    return (pltpu.make_async_copy(k_hbm.at[pidx], k_buf.at[slot, :, g],
                                  sem.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[pidx], v_buf.at[slot, :, g],
                                  sem.at[slot, 1]))


def _paged_decode_kernel(lens_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sem, slot_ref, *, page, C, max_pages,
                         scale, batch, window=None):
    bi = pl.program_id(0)
    hkv, group, d = q_ref.shape[1:]
    CT = C * page

    def first_page(row):
        """The first page a windowed row's walk reads, the one that holds
        position ``len - window``: the pages behind it are not read."""
        return jnp.maximum(lens_ref[row] - window, 0) // page

    def row_pages(row):
        n = jnp.minimum((lens_ref[row] + page - 1) // page, max_pages)
        return n if window is None else n - first_page(row)

    copies = functools.partial(_page_copies, k_hbm, v_hbm, k_buf, v_buf, sem)

    def start_chunk(slot, row, c, n):
        """Fetch the first ``n`` pages of chunk ``c`` of ``row``: one DMA a
        page and pool side, [kv_heads, page, d] each."""
        def body(g, _):
            at = row * max_pages + c * C + g
            if window is not None:
                at = at + first_page(row)
            pidx = jnp.maximum(tables_ref[at], 0)
            for cp in copies(slot, pidx, g):
                cp.start()
            return 0
        jax.lax.fori_loop(0, n, body, 0)

    def wait_chunk(slot, n):
        def body(g, _):
            for cp in copies(slot, 0, g):
                cp.wait()
            return 0
        jax.lax.fori_loop(0, n, body, 0)

    @pl.when(bi == 0)
    def _():
        # nobody fetched for the first row. The buffers start as zeros: a
        # row's last chunk fetches only the pages that hold context, what the
        # rest of the buffer holds meets p == 0, and 0 * x is 0 only for a
        # finite x (scratch memory is not initialised)
        slot_ref[0] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        start_chunk(0, 0, 0, jnp.minimum(row_pages(0), C))

    ctx = lens_ref[bi]
    n_pages = row_pages(bi)
    n_chunks = (n_pages + C - 1) // C
    slot0 = slot_ref[0]
    nxt_row = jnp.minimum(bi + 1, batch - 1)
    nxt_first = jnp.where(bi + 1 < batch,
                          jnp.minimum(row_pages(nxt_row), C), 0)

    # bf16 q and K go to the MXU as stored (float32 accumulation, the scale
    # applied to the scores); anything else is computed from float32
    q = q_ref[0]                                            # [hkv, group, d]
    if not q.dtype == k_buf.dtype == jnp.bfloat16:
        q = q.astype(jnp.float32)

    def chunk_step(c, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + c) % 2
        # fetch what is computed next while this chunk computes: the row's
        # next chunk, or after its last one the next row's first
        last = c + 1 == n_chunks
        start_chunk(1 - slot, jnp.where(last, nxt_row, bi),
                    jnp.where(last, 0, c + 1),
                    jnp.where(last, nxt_first,
                              jnp.minimum(n_pages - (c + 1) * C, C)))
        wait_chunk(slot, jnp.minimum(n_pages - c * C, C))

        k = k_buf[slot].reshape(hkv, CT, d).astype(q.dtype)
        # p.V from float32 p and V, as before; Mosaic's default-precision
        # float32 dot is one bf16 pass on the MXU (PERF.md section 6, PR 25)
        v = v_buf[slot].reshape(hkv, CT, d).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        pos = c * CT + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if window is None:
            keep = pos < ctx
        else:
            # the walk starts mid-window's first page: the positions of it
            # that lie behind the window are masked
            pos = pos + first_page(bi) * page
            keep = (pos < ctx) & (pos >= ctx - window)
        s = jnp.where(keep, s, NEG_INF)                     # [hkv, group, CT]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk_step,
        (jnp.full((hkv, group, 1), NEG_INF, jnp.float32),
         jnp.zeros((hkv, group, 1), jnp.float32),
         jnp.zeros((hkv, group, d), jnp.float32)))
    slot_ref[0] = (slot0 + n_chunks) % 2

    @pl.when(n_chunks == 0)
    def _():
        # an empty row fetched nothing and computes nothing, but the next row
        # still counts on this step for its first chunk
        start_chunk(slot0, nxt_row, 0, nxt_first)

    # zero-length rows (freed/parked slots) emit zeros, not garbage — callers
    # may rely on inactive rows being inert
    out = acc / jnp.where(l > 0, l, 1.0)
    o_ref[0] = jnp.where(ctx > 0, out, 0.0).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _decode_call(b, hkv, group, w, page, C, max_pages, scale, q_dtype,
                 k_dtype, v_dtype, interpret, window=None):
    """The ``pallas_call`` of one shape class, made once: every layer and
    every program of an engine calls the same object, so jax traces the
    kernel's body once a class and not once a call."""
    kernel = functools.partial(
        _paged_decode_kernel, page=page, C=C, max_pages=max_pages,
        scale=scale, batch=b, **({} if window is None else
                                 {"window": int(window)}))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, group, w), lambda bi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, w),
                               lambda bi, *_: (bi, 0, 0, 0)),
        scratch_shapes=[
            # [slot, kv head, page of the chunk, token, w]: a head's chunk is
            # one [chunk_tokens, w] tile, a page's DMA lands in every head's
            pltpu.VMEM((2, hkv, C, page, w), k_dtype),
            pltpu.VMEM((2, hkv, C, page, w), v_dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="pt_paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, w), q_dtype),
        # "arbitrary": the prefetch chain carries the buffer slot and the
        # DMAs in flight from one row to the next, so the rows may not
        # be split across cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)


def _kernel_takes(pool) -> bool:
    """Whether ``pt_paged_decode`` reads this pool, and with it whether the
    append scatters rows in place: Mosaic's page DMA needs a 128-aligned
    trailing dim and a sublane-aligned page dim. Heads of 128 have that in
    the logical form; narrower heads have it lane-dense, ``128 // d`` KV
    heads to a row (``kv_pool_shape``), and there only: a logical pool of
    narrow heads, like every int8 pool, goes to the dense gather and the
    slot-major scatter. On the v5e a call takes 0.139 ms at 24 rows x 8
    heads of 128 and 1,000 tokens a row (87% of its bytes' time) and 0.303
    ms at 64 rows x 8 heads of 64 and the chat-batch-64 cell's contexts
    (55%: 16 KB a page DMA for 32; the gather it replaces 1.27 ms; PERF.md
    section 6, PR 32)."""
    _, _, page, w = pool.shape
    return (not isinstance(pool, QuantizedKVPool)
            and w % _LANES == 0 and page % _sublanes(pool.dtype) == 0)


def paged_decode_attention(q, k_cache, v_cache, block_tables, context_lens,
                           scale=None, interpret: bool = False,
                           window: Optional[int] = None):
    """One-token-per-sequence paged decode.

    ``window`` (static; None: the whole history): the query, at position
    ``len - 1``, sees positions ``>= len - window`` only. The kernel starts
    its walk at the page that holds ``len - window`` and masks what lies
    behind the window in that page: the pages behind it are not read, and
    their table entries may be stale (a sequence gives those pages back
    while it lives: docs/SERVING.md "Window and full layers").

    q: [batch, q_heads, head_dim]; caches [num_pages, kv_heads, page, d] or
    lane-dense [num_pages, kv_heads // f, page, 128], f = 128 // d, which
    the one kernel reads as ``kv_heads // f`` head groups of width 128: a
    head's query rows sit in its own d lanes with zeros beside them, so
    ``q.K^T`` is each head's own scores, and of ``p.V`` each head keeps its
    own lanes (0.303 ms a call at 64 rows x 32/8 heads of 64 on the v5e,
    0.139 ms at 24 rows x 16/8 heads of 128: PERF.md section 6, PR 32);
    block_tables [batch, max_pages_per_seq] int32; context_lens [batch] int32
    (number of valid cache tokens INCLUDING the current position's k/v, which
    must already be appended via append_paged_kv; rows with length 0 return
    ZEROS — freed/parked serving slots are guaranteed inert). Returns
    [batch, hq, d].

    On a TPU every bf16/float32 pool whose pages Mosaic can slice runs the
    Pallas kernel, short tables included: the rule that sent rows of fewer
    than two chunks to the XLA gather was written for the per-(row, head)
    chain (3 ms at 8 rows x 16 heads x 2 pages) and is gone. Measured on the
    v5e, MHA 16/16 over 8 rows: 8 pages a row, ragged to 128 tokens, kernel
    12.9 us against the gather's 19.5 (the old kernel 133); 2 pages a row,
    9.2 us against 7.0 — 2 us a call is not worth a second path.
    """
    b, hq, d = q.shape
    if scale is None:
        scale = d ** -0.5
    # int8 block format: the Pallas kernel does not carry the per-block
    # dequant yet — the dense-gather reference dequantizes in the gather
    # (open TPU-kernel work), and serves whatever Mosaic cannot slice
    if isinstance(k_cache, QuantizedKVPool) or not interpret and (
            jax.default_backend() != "tpu" or not _kernel_takes(k_cache)):
        return paged_decode_reference(q, k_cache, v_cache, block_tables,
                                      context_lens, scale, window=window)
    f = _pool_fold(k_cache, d)
    _, hkv, page, w = k_cache.shape       # hkv head groups of f heads each
    group = hq // hkv                     # query rows a head group
    if f > 1:
        # head j*f + i of group j: its query rows in lanes i*d .. (i+1)*d,
        # zeros in the lanes of the group's other heads
        eye = jnp.eye(f, dtype=q.dtype)[:, None, :, None]
        q = q.reshape(b, hkv, f, group // f, 1, d) * eye
    max_pages = block_tables.shape[1]
    C = _decode_chunk_pages(max_pages, hkv, page, w,
                            jnp.dtype(k_cache.dtype).itemsize)

    call = _decode_call(b, hkv, group, w, page, C, max_pages, float(scale),
                        jnp.dtype(q.dtype), jnp.dtype(k_cache.dtype),
                        jnp.dtype(v_cache.dtype), interpret,
                        *(() if window is None else (int(window),)))
    # the kernel's name reaches the HLO instruction and the scope its name
    # stack: traces find the kernel by name, not by a shape
    with jax.named_scope("pt_paged_decode"):
        out = call(context_lens, block_tables.reshape(-1),
                   q.reshape(b, hkv, group, w), k_cache, v_cache)
    if f > 1:
        # of a head's rows, the lanes that hold its own V
        out = out.reshape(b, hkv, f, group // f, f, d)
        out = jnp.stack([out[:, :, i, :, i] for i in range(f)], axis=2)
    return out.reshape(b, hq, d)


# ---------------------------------------------------------------------------
# chunk prefill over cached history (prefix cache / chunked-prefill path)
# ---------------------------------------------------------------------------

def window_chunk_pages(window: int, s: int, page: int, max_pages: int) -> int:
    """Pages of a row's table that a chunk of ``s`` queries on a layer with
    a ``window`` can see: from the page of ``start - window + 1`` to the
    page of ``start + s - 1``, ``ceil((window + s) / page) + 1`` at most,
    and never more than the table."""
    return min(-(-(window + s) // page) + 1, max_pages)


def paged_prefill_reference(q, k_cache, v_cache, block_tables, chunk_starts,
                            scale=None, window: Optional[int] = None):
    """:func:`paged_prefill_attention` as a dense gather and one float32
    softmax (tests, the CPU, int8 pools, whatever Mosaic cannot slice): a
    full layer gathers and scores the table's whole ``max_pages * page``
    extent for every row, a window layer the ``window_chunk_pages`` pages
    from the page of ``start - window + 1``; the mask is by absolute
    position."""
    b, s, hq, d = q.shape
    page = k_cache.shape[2]
    hkv = k_cache.shape[1] * _pool_fold(k_cache, d)
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    max_pages = block_tables.shape[1]
    L = max_pages * page
    if window is not None:
        n = window_chunk_pages(int(window), s, page, max_pages)
        L = n * page
        first = jnp.maximum(chunk_starts - (int(window) - 1), 0) // page
        idx = first[:, None] + jnp.arange(n)             # [b, n] page index
        # past the table's end: any page, its positions lie past every query
        block_tables = jnp.take_along_axis(
            block_tables, jnp.minimum(idx, max_pages - 1), axis=1)
        key_pos = (first * page)[:, None] + jnp.arange(L)[None, :]
    safe_tables = jnp.maximum(block_tables, 0)
    kg = jnp.swapaxes(_gather_pages(k_cache, safe_tables, d),
                      2, 3).reshape(b, L, hkv, d)
    vg = jnp.swapaxes(_gather_pages(v_cache, safe_tables, d),
                      2, 3).reshape(b, L, hkv, d)
    kg = jnp.swapaxes(kg, 1, 2).astype(jnp.float32)      # [b, hkv, L, d]
    vg = jnp.swapaxes(vg, 1, 2).astype(jnp.float32)
    qf = q.reshape(b, s, hkv, group, d).astype(jnp.float32)
    qf = jnp.transpose(qf, (0, 2, 3, 1, 4))              # [b, hkv, g, s, d]
    sc = jnp.einsum("bhgsd,bhld->bhgsl", qf, kg) * scale
    q_pos = chunk_starts[:, None] + jnp.arange(s)        # [b, s] absolute
    if window is None:
        keep = (jnp.arange(L)[None, None, :]
                <= q_pos[:, :, None])                    # [b, s, L]
    else:
        keep = ((key_pos[:, None, :] <= q_pos[:, :, None])
                & (key_pos[:, None, :] > q_pos[:, :, None] - int(window)))
    sc = jnp.where(keep[:, None, None, :, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgsl,bhld->bhgsd", p, vg)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s, hq, d)
    return out.astype(q.dtype)


#: the longest block of keys the chunk kernel scores at once (a function of
#: nothing a call's ``s`` or row count changes: the blocks are part of what
#: a query's bits depend on; on the v5e blocks of 256 / 512 keys ran 1.62 /
#: 1.33 ms a call on trinity's full layer and 1.08 / 0.96 on its window
#: layer: PERF.md section 6, PR 42), the query rows of a KV head scored at
#: once (what bounds the float32 scores in VMEM: 4 MB a tile), the longest
#: chunk the kernel takes and the VMEM it may take (32 query heads x 512
#: queries hold 42 MB of it: the row's q, output, accumulator, maximum, sum)
_CHUNK_MAX_BLOCK_TOKENS = 512
_CHUNK_QUERY_TILE = 2048
_CHUNK_MAX_QUERIES = 512
_CHUNK_VMEM_LIMIT = 64 << 20


def _paged_chunk_kernel(starts_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                        k_buf, v_buf, sem, slot_ref, m_ref, l_ref, acc_ref,
                        *, page, C, max_pages, scale, batch, s, window):
    """One grid step a chunk row: ``s`` queries at positions ``start + i``,
    ``R = group * s`` query rows a KV head (row ``r`` is query ``r % s``),
    against the row's own pages, block ``j`` of the walk holding the
    ABSOLUTE positions ``[j * CT, (j + 1) * CT)``."""
    bi = pl.program_id(0)
    hkv, R, w = q_ref.shape[1:]
    CT = C * page

    def span(row):
        """``(first, end)``: the pages ``[first, end)`` a row's walk reads,
        from the page of ``start - window + 1`` (0 on a full layer) to the
        page of its last query."""
        start = starts_ref[row]
        end = jnp.minimum((start + s + page - 1) // page, max_pages)
        if window is None:
            return 0, end
        return jnp.maximum(start - (window - 1), 0) // page, end

    copies = functools.partial(_page_copies, k_hbm, v_hbm, k_buf, v_buf, sem)

    def block_pages(row, j, on=True):
        """The pages of ``row``'s walk that lie in block ``j`` (none where
        ``on`` is false)."""
        first, end = span(row)
        lo = jnp.maximum(j * C, first)
        return lo, jnp.where(on, jnp.maximum(jnp.minimum((j + 1) * C, end),
                                             lo), lo)

    def start_block(slot, row, j, on=True):
        def body(pg, _):
            pidx = jnp.maximum(tables_ref[row * max_pages + pg], 0)
            for cp in copies(slot, pidx, pg - j * C):
                cp.start()
            return 0
        jax.lax.fori_loop(*block_pages(row, j, on), body, 0)

    def wait_block(slot, row, j):
        def body(pg, _):
            for cp in copies(slot, 0, pg - j * C):
                cp.wait()
            return 0
        jax.lax.fori_loop(*block_pages(row, j), body, 0)

    def first_block(row):
        return span(row)[0] // C

    @pl.when(bi == 0)
    def _():
        # as the decode kernel: nobody fetched for the first row, and what a
        # block's buffer holds beside the pages fetched meets p == 0, which
        # makes 0 only of a finite number
        slot_ref[0] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        start_block(0, 0, first_block(0))

    start = starts_ref[bi]
    j0 = first_block(bi)
    j1 = (span(bi)[1] + C - 1) // C                 # one past the last block
    slot0 = slot_ref[0]
    nxt_row = jnp.minimum(bi + 1, batch - 1)
    nxt_j0 = first_block(nxt_row)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q_pos = start + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0), s)

    def heads(slot, key0, masked):
        if masked:
            key = key0 + jax.lax.broadcasted_iota(jnp.int32, (1, CT), 1)
        for h in range(hkv):
            k = k_buf[slot, h].reshape(CT, w)
            v = v_buf[slot, h].reshape(CT, w).astype(jnp.float32)
            for t in range(0, R, _CHUNK_QUERY_TILE):
                tile(h, slice(t, min(t + _CHUNK_QUERY_TILE, R)), k, v,
                     key if masked else None)

    def tile(h, rows, k, v, key):
        """A tile of a head's query rows against one block of keys; ``key``
        the keys' positions on the path that masks, else None."""
        # bf16 q and K go to the MXU as stored (float32 accumulation);
        # anything else is computed from float32. p.V from float32 p and V,
        # as in the decode kernel
        q = q_ref[0, h, rows]                               # [TQ, w]
        if not q.dtype == k.dtype == jnp.bfloat16:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if key is not None:
            keep = key <= q_pos[rows]                       # [TQ, CT]
            if window is not None:
                keep &= key > q_pos[rows] - window
            sc = jnp.where(keep, sc, NEG_INF)
        # the running maximum is of the scores BEFORE the scale (which is
        # positive), and the scale multiplies the difference: a visible key
        # then goes through the same subtract, multiply and exp on the
        # masked and on the unmasked path, with no product beside a sum for
        # a compiler to contract on one of them alone
        m_prev = m_ref[h, rows]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp((sc - m_new) * scale)
        if key is not None:
            # a block that is wholly masked for a query row leaves that
            # row's m, l and accumulator bit for bit alone: m_new is m_prev,
            # alpha 1 and p 0 (exp(NEG_INF - NEG_INF) is 1)
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp((m_prev - m_new) * scale)
        l_ref[h, rows] = (l_ref[h, rows] * alpha
                          + jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[h, rows] = acc_ref[h, rows] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h, rows] = m_new

    def block_step(j, _):
        slot = (slot0 + j - j0) % 2
        # fetch what is computed next while this block computes: the row's
        # next block, or after its last one the next row's first
        last = j + 1 == j1
        start_block(1 - slot, jnp.where(last, nxt_row, bi),
                    jnp.where(last, nxt_j0, j + 1),
                    jnp.logical_or(jnp.logical_not(last), bi + 1 < batch))
        wait_block(slot, bi, j)
        key0 = j * CT
        # every key of the block seen by every query of the row: no mask
        inner = key0 + CT - 1 <= start
        if window is not None:
            inner &= key0 > start + s - 1 - window
        pl.when(inner)(lambda: heads(slot, key0, False))
        pl.when(jnp.logical_not(inner))(lambda: heads(slot, key0, True))
        return 0

    jax.lax.fori_loop(j0, j1, block_step, 0)
    slot_ref[0] = (slot0 + j1 - j0) % 2
    for h in range(hkv):
        l = l_ref[h]
        o_ref[0, h] = (acc_ref[h] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _chunk_call(b, hkv, R, w, s, page, C, max_pages, scale, q_dtype, k_dtype,
                v_dtype, interpret, window):
    """The ``pallas_call`` of one shape class, made once (``_decode_call``)."""
    kernel = functools.partial(
        _paged_chunk_kernel, page=page, C=C, max_pages=max_pages, scale=scale,
        batch=b, s=s, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, R, w), lambda bi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, R, w), lambda bi, *_: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, C, page, w), k_dtype),
            pltpu.VMEM((2, hkv, C, page, w), v_dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            # the online softmax of a row: running maximum, sum, accumulator
            pltpu.VMEM((hkv, R, 1), jnp.float32),
            pltpu.VMEM((hkv, R, 1), jnp.float32),
            pltpu.VMEM((hkv, R, w), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="pt_paged_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, R, w), q_dtype),
        # "arbitrary": the prefetch chain runs from row to row
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret)


def _chunk_kernel_takes(pool, s: int) -> bool:
    """Whether ``pt_paged_chunk`` serves a chunk of ``s`` queries a row over
    this pool: a pool the decode kernel reads (``_kernel_takes``) and an
    ``s`` of whole sublane tiles (a speculative verify window of ``K + 1``
    positions goes to the reference) whose row state fits the VMEM."""
    return (_kernel_takes(pool) and s % 8 == 0
            and s <= _CHUNK_MAX_QUERIES)


def chunk_kernel_layers(kv, chunk_tokens: int) -> int:
    """Layers that keep K and V whose chunk form runs ``pt_paged_chunk`` at
    ``chunk_tokens`` queries a row: none off the TPU."""
    if jax.default_backend() != "tpu":
        return 0
    return sum(_chunk_kernel_takes(e[0], chunk_tokens) for e in kv
               if _kind(e) == "kv")


def paged_prefill_attention(q, k_cache, v_cache, block_tables, chunk_starts,
                            scale=None, window: Optional[int] = None,
                            interpret: bool = False):
    """Attention for a prefill CHUNK whose rows sit at per-row absolute
    offsets inside already-partially-filled paged caches.

    q: [b, s, hq, d] — queries for tokens at absolute positions
    ``chunk_starts[b] + i`` (i in [0, s)); the chunk's own k/v must already
    be appended into the pages (append-then-read, so within-chunk keys and
    the cached prefix are read through ONE code path). Returns [b, s, hq, d].

    A query at position p attends keys at positions <= p, and with a
    ``window`` (static; None: the whole history) keys ``p - window < j <=
    p``. On a TPU, for a pool the decode kernel reads and an ``s`` of whole
    tiles (``_chunk_kernel_takes``), the Pallas kernel ``pt_paged_chunk``
    walks each row's OWN pages with an online softmax, as ``pt_paged_decode``
    does for one query: from the page of ``start - window + 1`` (0 on a
    full layer) to the page of the row's last query, K and V pages fetched
    by DMA a block ahead, scores kept in VMEM. Nothing past a row's last
    query is fetched and nothing behind the window: those table entries may
    be stale. Everything else (the CPU, int8 pools, pools Mosaic cannot
    slice) runs :func:`paged_prefill_reference`, the dense gather.

    What the serving engine's warm == cold token-equality guarantee rests
    on: **the kernel's key blocks are aligned to ABSOLUTE positions** (block
    ``j`` holds positions ``[j * CT, (j + 1) * CT)``, ``CT`` a function of
    the pool's shape alone), the mask depends on absolute positions only,
    and a block wholly masked for a query leaves its running maximum, sum
    and accumulator bit for bit alone. So a query meets the same blocks in
    the same order however its prompt was chunked and however much of it
    the prefix cache supplied: GIVEN the same cached k/v bytes its output
    is bit-identical (the reference has the property for its own reason: one
    softmax over an extent fixed per engine). The engine's module docstring
    scopes what "same bytes" means at re-stepped block-final positions.
    Rows are independent, so several rows may SHARE one sequence's block
    table at different ``chunk_starts`` — the engine's prompt-packing
    prefill flattens (slot, chunk) pairs into the rows of one call; because
    every row's k/v is appended before any row reads, a later chunk reads an
    earlier chunk's pages written in the same program, bit-identical to
    sequential chunk calls.

    ``interpret`` reaches the kernel off the TPU (the tests)."""
    b, s, hq, d = q.shape
    if scale is None:
        scale = d ** -0.5
    # the kernel takes its running maximum before the scale: a positive one
    if isinstance(k_cache, QuantizedKVPool) or scale <= 0 or (
            not interpret and (jax.default_backend() != "tpu"
                               or not _chunk_kernel_takes(k_cache, s))):
        return paged_prefill_reference(q, k_cache, v_cache, block_tables,
                                       chunk_starts, scale, window=window)
    f = _pool_fold(k_cache, d)
    _, hkv, page, w = k_cache.shape       # hkv head groups of f heads each
    group = hq // hkv                     # query heads a head group
    # [b, hkv, f, group // f, s, d]: a head group's query rows, query i of
    # each head at row r with r % s == i
    qt = jnp.transpose(q.reshape(b, s, hkv, f, group // f, d),
                       (0, 2, 3, 4, 1, 5))
    if f > 1:
        # lane-dense pools, as the decode kernel reads them: head j*f + i of
        # group j has its query rows in lanes i*d .. (i+1)*d, zeros beside
        eye = jnp.eye(f, dtype=q.dtype)[:, None, None, :, None]
        qt = qt[..., None, :] * eye
    max_pages = block_tables.shape[1]
    C = _decode_chunk_pages(max_pages, hkv, page, w,
                            jnp.dtype(k_cache.dtype).itemsize,
                            _CHUNK_MAX_BLOCK_TOKENS)
    call = _chunk_call(b, hkv, group * s, w, s, page, C, max_pages,
                       float(scale), jnp.dtype(q.dtype),
                       jnp.dtype(k_cache.dtype), jnp.dtype(v_cache.dtype),
                       interpret, None if window is None else int(window))
    # the kernel's name reaches the HLO instruction and the scope its name
    # stack: traces find the kernel by name, not by a shape
    with jax.named_scope("pt_paged_chunk"):
        out = call(chunk_starts, block_tables.reshape(-1),
                   qt.reshape(b, hkv, group * s, w), k_cache, v_cache)
    # of a head's rows, the lanes that hold its own V
    out = out.reshape(b, hkv, f, group // f, s, f, d)
    out = jnp.stack([out[:, :, i, :, :, i] for i in range(f)], axis=2)
    return jnp.transpose(out, (0, 4, 1, 2, 3, 5)).reshape(b, s, hq, d)


def paged_verify_attention(q, k_cache, v_cache, block_tables, row_starts,
                           scale=None, window: Optional[int] = None):
    """Speculative-decode VERIFY attention: score a K+1-token draft window
    per row in ONE pass (inference/serving.py speculative mega-step).

    q: [b, s, hq, d] — queries for the window [last_token, draft_1..draft_K]
    whose rows sit at per-row absolute offsets ``row_starts[b] + i`` inside
    already-partially-filled paged caches. The window's own k/v must
    already be appended (append-then-read), exactly the
    :func:`paged_prefill_attention` machinery — which is what this
    delegates to: the absolute-position mask means window position i
    attends the cached prefix plus drafts 0..i, so the logits at position
    i are what a sequential ``paged_token_step`` at that position would
    compute given the same cache bytes (the same keys under the same mask;
    bit for bit where both run the reference, as on the CPU) — the greedy
    byte-identity guarantee of speculative decoding rests here. Rejected
    drafts' appended k/v needs no explicit rollback: positions past the
    accepted prefix sit beyond the advanced context length, are never
    attended, and are overwritten as decode proceeds (the engine's standard
    pad-append invariant). int8 pools dequantize in the gather like every
    other read path.

    NOTE this is a NAMED THIN DELEGATION: the production verify program
    (``paged_verify_step`` -> layer ``paged_prefill_chunk``) dispatches
    the shared :func:`paged_prefill_attention` directly — verify and chunk
    prefill are deliberately ONE implementation and ONE dispatch: a window
    of ``K + 1`` positions is no whole tile of queries, so it runs
    :func:`paged_prefill_reference` everywhere (``_chunk_kernel_takes``),
    a chunk of whole tiles the kernel on a TPU. Behavioral changes belong
    in that shared body; changing only this wrapper changes tests, not
    serving."""
    return paged_prefill_attention(q, k_cache, v_cache, block_tables,
                                   row_starts, scale, window=window)


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
    __slots__ = ("children", "block", "parent", "key", "last_used", "depth")

    def __init__(self, parent=None, key=None, block=None):
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.key = key
        self.block = block
        self.last_used = 0
        self.depth = 0 if parent is None else parent.depth + 1


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
        # called with a node's block as the node leaves the trie
        # (PageGroups: the pages the other groups keep for that node)
        self.on_evict: Optional[Callable[[int], None]] = None
        allocator.is_cached = self.has_block

    def __len__(self) -> int:
        return len(self._by_block)

    def has_block(self, block: int) -> bool:
        return block in self._by_block

    def chain(self, tokens) -> List[int]:
        """The blocks of the nodes along ``tokens``' full pages, as far as
        the trie holds them; counts and touches nothing."""
        node, out = self.root, []
        for key in self._chunks(tokens):
            node = node.children.get(key)
            if node is None:
                break
            out.append(node.block)
        return out

    def recency(self, block: int) -> tuple:
        """``(last_used, -depth)`` of the node that holds ``block``: the
        order in which another group's pages of the trie are given up
        (least recently used first, the deepest of a path first)."""
        node = self._by_block[block]
        return node.last_used, -node.depth

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
            if self.on_evict is not None:
                self.on_evict(victim.block)
            self.allocator.free_cached(victim.block)
            self.evictions += 1
            freed += 1
        return freed


class PageGroup:
    """The pages of one layer KIND: its window (None: the whole history),
    its own page count, allocator and parking page."""

    __slots__ = ("kind", "window", "num_blocks", "alloc", "park",
                 "slot_pages")

    def __init__(self, kind: str, window: Optional[int], num_blocks: int,
                 slot_pages: int):
        self.kind = kind
        self.window = None if window is None else int(window)
        self.num_blocks = int(num_blocks)
        self.alloc = BlockAllocator(self.num_blocks)
        self.park = self.num_blocks        # the page after the allocator's
        self.slot_pages = int(slot_pages)  # the most a sequence holds

    @property
    def in_use(self) -> int:
        """Pages mapped by a live sequence (held and cached-idle pages are
        not in use)."""
        return len(self.alloc._ref)


def group_table(tables, g: int):
    """Group ``g``'s part of what ``PageGroups`` hands a program: with one
    group the one array, otherwise a sequence of one a group."""
    return tables[g] if isinstance(tables, (tuple, list)) else tables


class PageGroups:
    """The page groups of an engine with a prefix cache, one a layer kind
    that keeps K and V (docs/SERVING.md "Window and full layers: a page
    group a kind"): the ONE place that knows a model has more than one.

    A model declares ``kv_groups() -> [(kind, window), ...]``, the full
    group (window None) first; a model without the method has the one full
    group, and every method here then does what the engine did before
    there were groups. Group 0 is sized by ``max_len``:
    ``max_batch * ceil(max_len / page) + extra_blocks`` pages. A window
    group never is: ``max_batch * slot_pages`` plus its share of
    ``extra_blocks``, with ``slot_pages = ceil((window + advance) / page)
    + 1``, where ``advance`` is the most positions one program moves a
    sequence: ``slot_rows`` rows of a packed call, and no less than a chunk
    or a decode block. The rule is a trade between two costs a row more
    brings: a slot mid-prefill decodes nothing, and it stays mid-prefill
    ``prompt / (slot_rows * chunk)`` packed calls; every row costs each
    slot ``chunk / page`` pages of every window pool, held whether a
    prompt is being written or not. ``slot_rows`` is what a quarter of the
    (least) window holds in whole chunks: the pool is then at most a
    quarter larger than the window's own pages, and a prompt of ``n``
    windows is ``4 n`` calls away from its first token, not ``window /
    chunk`` times ``n``. (One row a slot and four were both read on the
    chip at one traffic mix: PERF.md section 6, PR 40.)

    Tables stay indexed by absolute page (``position // page``) in every
    group, so the appends, copy-on-write and the chunk's gather address
    alike. A sequence's window-group row is filled as it goes: ``reserve``
    maps fresh pages ahead of the next program, ``release_behind`` gives
    back (decref) the pages that lie wholly behind ``pos - window`` after
    it; their entries stay in the row, stale, because no reader looks
    behind the window. ``slot_pages`` is what a sequence holds between the
    two at most, so with every slot busy ``reserve`` still finds pages
    (cached-idle ones are evicted for it).

    The trie stays the full group's (``RadixPrefixCache`` over its
    allocator, a node a page); what a window group keeps for a node hangs
    beside it by the node's block (``_side``). A window group's cached
    pages are given up on their own, least recently used first and the
    deepest of a path first, so a chain may lack them in the middle: a hit
    of ``n`` pages is honoured where the window group still covers pages
    ``[max(0, n * page - window) // page, n)`` (``honour``)."""

    def __init__(self, decl, *, max_batch: int, max_len: int, page_size: int,
                 chunk: int, block: int, extra_blocks: int = 0):
        decl = [(str(k), None if w is None else int(w)) for k, w in decl]
        if not decl or decl[0][1] is not None or any(
                w is None or w < 1 for _, w in decl[1:]):
            raise ValueError(
                f"kv_groups must name the full group (window None) first "
                f"and a window for every other: {decl}")
        self.page = int(page_size)
        self.maxp = -(-int(max_len) // self.page)
        windows = [w for _, w in decl[1:]]
        self.slot_rows = max(1, min(windows) // 4 // int(chunk)) \
            if windows else None
        self.advance = max(int(chunk) * (self.slot_rows or 1), int(block))
        extra = max(0, int(extra_blocks))
        self.groups: List[PageGroup] = []
        for kind, window in decl:
            if window is None:
                per, n = self.maxp, max_batch * self.maxp + extra
            else:
                per = min(self.maxp,
                          -(-(window + self.advance) // self.page) + 1)
                n = max_batch * per + -(-extra * per // self.maxp)
            self.groups.append(PageGroup(kind, window, n, per))
        self.full = self.groups[0]
        self.windowed = self.groups[1:]
        self.radix = RadixPrefixCache(self.page, self.full.alloc)
        # per window group: full block of a trie node -> the group's block
        # for that node, and back; per slot: absolute page -> block held,
        # the table row, and the pages reserved from page 0 on
        self._side = [dict() for _ in self.windowed]
        self._back = [dict() for _ in self.windowed]
        self._held = [[None] * max_batch for _ in self.windowed]
        self._rows = [[None] * max_batch for _ in self.windowed]
        self._front = [[0] * max_batch for _ in self.windowed]
        self.released = 0          # window pages given back by a live slot
        self.allocated = 0         # window pages mapped fresh
        self.shortened = 0         # hits honoured shorter than matched
        for gi, g in enumerate(self.windowed):
            g.alloc.is_cached = self._back[gi].__contains__
        if self.windowed:
            self.radix.on_evict = self._node_left

    # -- what the engine builds from ---------------------------------------
    @property
    def single(self) -> bool:
        return not self.windowed

    def pool_pages(self) -> List[int]:
        """Pages each group's pools are asked for: the allocator's and the
        parking page."""
        return [g.num_blocks + 1 for g in self.groups]

    def parts(self, per_group):
        """One value a group as the programs take them: the value itself
        with one group (what the engine kept before there were groups), a
        tuple otherwise. With ``group_table``, its inverse, the only place
        that tells the two apart."""
        return per_group[0] if self.single else tuple(per_group)

    def _each(self, fn, *parts):
        """``parts`` of ``fn(group, *the group's part of each argument)``."""
        return self.parts([fn(g, *(group_table(p, i) for p in parts))
                           for i, g in enumerate(self.groups)])

    def parked(self, n: Optional[int] = None):
        """Table rows that map every page to the parking page: ``[maxp]``
        (``n`` None) or ``[n, maxp]`` int32."""
        shape = (self.maxp,) if n is None else (n, self.maxp)
        return self._each(lambda g: np.full(shape, g.park, np.int32))

    def put(self, dst, j: int, rows) -> None:
        """``dst[j] = rows`` for what ``parked(n)`` and ``rows`` return."""
        self._each(lambda g, d, r: d.__setitem__(j, r), dst, rows)

    def rows(self, slot: int, full_row):
        """A slot's table rows: the full group's as the engine keeps it and
        each window group's as reserved so far."""
        return self.parts([full_row] + [r[slot].copy() for r in self._rows])

    def prompt_rows(self, rows, n_real: int):
        """``rows`` with everything past the prompt's ``n_real`` pages
        parked (the engine's ``_prefill_row``)."""
        def cut(g, row):
            out = np.full(self.maxp, g.park, np.int32)
            out[:n_real] = row[:n_real]
            return out

        return self._each(cut, rows)

    def copy_pages(self, kv, layer_groups, src, dst):
        """``copy_layer_pages`` over every layer, each with its group's part
        of ``src`` / ``dst`` (``parts`` of one [width] vector a group)."""
        return [copy_layer_pages(e, group_table(src, g), group_table(dst, g))
                for e, g in zip(kv, layer_groups)]

    # -- the hit rule -------------------------------------------------------
    def _first_seen(self, g: PageGroup, n_pages: int) -> int:
        """The first page a query at position ``n_pages * page`` reads in a
        window group."""
        return max(0, n_pages * self.page - g.window) // self.page

    def honour(self, matched: List[int]) -> List[int]:
        """The longest head of a matched chain that every window group
        still covers where the request will read it."""
        n = len(matched)
        for gi, g in enumerate(self.windowed):
            side = self._side[gi]
            gap, last_gap = [], -1         # last page < i that g lacks
            for b in matched[:n]:
                gap.append(last_gap)
                if b not in side:
                    last_gap = len(gap) - 1
            gap.append(last_gap)
            while n and gap[n] >= self._first_seen(g, n):
                n -= 1
        if n < len(matched):
            self.shortened += 1
        return matched[:n]

    # -- a slot's window pages ---------------------------------------------
    def _evict(self, gi: int, n: int) -> int:
        """Give up to ``n`` of window group ``gi``'s cached-idle pages back
        to its free list."""
        g, back = self.windowed[gi], self._back[gi]
        idle = [b for b in back if g.alloc.refcount(b) == 0]
        idle.sort(key=lambda b: self.radix.recency(back[b]))
        for b in idle[:n]:
            del self._side[gi][back.pop(b)]
            g.alloc.free_cached(b)
        return min(n, len(idle))

    def _node_left(self, full_block: int) -> None:
        """The trie gave a node up: what the window groups kept for it goes
        with it (a page a live sequence still maps goes when that lets it
        go)."""
        for gi, g in enumerate(self.windowed):
            b = self._side[gi].pop(full_block, None)
            if b is not None:
                del self._back[gi][b]
                if g.alloc.refcount(b) == 0:
                    g.alloc.free_cached(b)

    def admit(self, slot: int, matched: List[int], cow_src: Optional[int],
              upto: int):
        """Map a newly admitted slot's window pages: the honoured chain's
        (shared), a private copy of ``cow_src``'s where the whole prompt
        hit, and fresh ones for positions below ``upto``. Returns the
        ``(src, dst)`` page copies the window groups need, one list a
        group, or None where a group is short of pages (nothing is kept:
        the admission defers)."""
        n = len(matched)
        copies = []
        for gi, g in enumerate(self.windowed):
            side = self._side[gi]
            lo = self._first_seen(g, n + (cow_src is not None))
            held = {i: side[matched[i]] for i in range(min(lo, n), n)}
            pinned = list(held.values())
            if cow_src is not None:
                pinned.append(side[cow_src])
            g.alloc.incref(pinned)
            self._held[gi][slot] = held
            row = np.full(self.maxp, g.park, np.int32)
            for i, b in held.items():
                row[i] = b
            self._rows[gi][slot] = row
            self._front[gi][slot] = n
            if not self._reserve(gi, slot, upto):
                g.alloc.decref(pinned)
                self._held[gi][slot] = self._rows[gi][slot] = None
                for gj in range(gi):
                    self._drop(gj, slot, copies[gj])
                return None
            copies.append([] if cow_src is None else
                          [(side[cow_src], self._held[gi][slot][n])])
        return copies

    def _drop(self, gi: int, slot: int, copies=()) -> None:
        g = self.windowed[gi]
        g.alloc.decref(list(self._held[gi][slot].values())
                       + [s for s, _ in copies])
        self._held[gi][slot] = self._rows[gi][slot] = None
        self._front[gi][slot] = 0

    def _reserve(self, gi: int, slot: int, upto: int) -> bool:
        g = self.windowed[gi]
        front = self._front[gi][slot]
        need = min(-(-int(upto) // self.page), self.maxp) - front
        if need <= 0:
            return True
        fresh = g.alloc.alloc(need, evict=lambda k: self._evict(gi, k))
        if fresh is None:
            return False
        held, row = self._held[gi][slot], self._rows[gi][slot]
        for i, b in enumerate(fresh, front):
            held[i] = row[i] = b
        self._front[gi][slot] = front + need
        self.allocated += need
        return True

    def reserve(self, slot: int, upto: int) -> Optional[bool]:
        """Map fresh window pages for the slot's positions below ``upto``.
        True: rows changed; False: nothing to do; None: a group is short of
        pages (what the earlier groups got stays theirs)."""
        changed = False
        for gi in range(len(self.windowed)):
            before = self._front[gi][slot]
            if not self._reserve(gi, slot, upto):
                return None
            changed |= self._front[gi][slot] != before
        return changed

    def reserved(self, slot: int) -> int:
        """Positions from 0 on that every window group has a page for."""
        return min((f[slot] for f in self._front), default=self.maxp) \
            * self.page

    def release_behind(self, slot: int, pos: int) -> int:
        """Give back the slot's window pages that no query at ``pos`` or
        later reads: those wholly below ``pos - window + 1``. Their row
        entries stay, stale."""
        n = 0
        for gi, g in enumerate(self.windowed):
            held = self._held[gi][slot]
            first = max(0, int(pos) - g.window + 1) // self.page
            gone = [i for i in held if i < first]
            if gone:
                g.alloc.decref([held.pop(i) for i in gone])
                n += len(gone)
        self.released += n
        return n

    def release(self, slot: int) -> None:
        """The slot is done: every window page it still maps."""
        for gi in range(len(self.windowed)):
            if self._held[gi][slot] is not None:
                self._drop(gi, slot)

    def written(self, slot: int, tokens, full_blocks) -> None:
        """Register the pages of ``tokens`` (whole pages of a prompt, all
        written) in the trie, each group's page under the node: the full
        group's first writer wins as before, and a node that lacks a window
        group's page takes this slot's."""
        self.radix.insert(tokens, full_blocks)
        if self.single:
            return
        chain = self.radix.chain(tokens)
        for gi in range(len(self.windowed)):
            held, side, back = (self._held[gi][slot], self._side[gi],
                                self._back[gi])
            for i, node_block in enumerate(chain):
                b = held.get(i)
                if b is not None and node_block not in side \
                        and b not in back:
                    side[node_block] = b
                    back[b] = node_block

    def shortfall(self, need_full: int) -> Optional[str]:
        """Why no admission can ever serve a request of ``need_full`` pages,
        or None."""
        if need_full > self.full.num_blocks:
            return (f"request needs {need_full} KV blocks but the pool holds "
                    f"{self.full.num_blocks}")
        return None


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
    the decode step). Returns updated (k_cache, v_cache). A pool the kernel
    reads (``_kernel_takes``: heads of 128, or narrower heads lane-dense,
    ``128 // d`` to a row) takes one row of 128 lanes a token and head
    group, in place in the default layout; any other pool the slot-major
    scatter that XLA's gather reads."""
    n_tokens = k_new.shape[0]
    page = k_cache.shape[2]
    if seq_ids is None:
        seq_ids = jnp.arange(n_tokens, dtype=jnp.int32)
    page_idx = block_tables[seq_ids, positions // page]      # [n]
    offs = positions % page                                   # [n]
    if isinstance(k_cache, QuantizedKVPool):
        return (_append_quantized(k_cache, k_new, page_idx, offs),
                _append_quantized(v_cache, v_new, page_idx, offs))
    groups, w = k_cache.shape[1], k_cache.shape[3]
    _pool_fold(k_cache, k_new.shape[-1])
    # a lane-dense pool's row holds f heads side by side: the new rows
    # [n, kv_heads, d] are [n, kv_heads // f, 128] as they stand
    k_new = k_new.reshape(n_tokens, groups, w)
    v_new = v_new.reshape(n_tokens, groups, w)
    if _kernel_takes(k_cache):
        # the kernel reads the default layout, and a scatter indexed on
        # page, head AND slot (one row of 128 lanes a token and head group)
        # keeps it, in place. Indexed on page and slot alone, XLA lays the
        # pools a scan carries out slot-major and converts each for the
        # kernel every token step: 18 of chat-batch's 30 ms (PERF.md
        # section 6, PR 30)
        heads = jnp.arange(groups, dtype=jnp.int32)[None, :]
        at = (page_idx[:, None], heads, offs[:, None])
    else:
        # XLA's own gather reads these pools (a width that does not fill
        # the lanes) and XLA picks one layout for the gather and the scatter
        at = (page_idx, slice(None), offs)
    return k_cache.at[at].set(k_new), v_cache.at[at].set(v_new)


def _appends_by_page(pool, s: int) -> bool:
    """Whether a page-aligned chunk of ``s`` tokens a row goes into ``pool``
    as whole page blocks: the pools the kernel reads (a page of a head group
    is one contiguous block in their default layout), whole pages only."""
    return _kernel_takes(pool) and s % pool.shape[2] == 0


def append_paged_chunk(k_cache, v_cache, k_new, v_new, block_tables, starts,
                       page_aligned: bool = False):
    """Scatter a chunk's tokens into the page pool: k_new/v_new [b, s,
    kv_heads, d], row ``r`` holding absolute positions ``starts[r] ..
    starts[r] + s - 1`` of the sequence whose table is ``block_tables[r]``.

    ``page_aligned`` (static) is the caller's word that every ``starts[r]``
    is a multiple of the page: the packed prefill gives it, a speculative
    verify window cannot. With it, in a pool the kernel reads and for ``s``
    a multiple of the page, a row's tokens are ``s // page`` whole pages,
    each one contiguous block ``[groups, page, 128]`` in the default layout,
    and go in as ``b * s // page`` updates indexed on the page alone, in
    place; pages past the table's width are dropped. The row form spends one
    update a token and head group on the same bytes, and the scatter is
    bound by update issue, not bytes: on the v5e, 8 x 128 tokens x 8 heads
    of 128 into a pool of 3,329 pages take 0.566 ms by the row (69 ns a
    row) and 0.016 ms by the page (PERF.md section 6, PR 35). Anything
    else (int8, a width that does not fill the lanes, a logical pool of
    narrow heads, ``s % page != 0``, no promise of alignment) is
    ``append_paged_kv`` at positions clipped into the table, as before."""
    b, s, nkv, d = k_new.shape
    page = k_cache.shape[2]
    if not (page_aligned and _appends_by_page(k_cache, s)):
        # pad rows of a final chunk land past the prompt; clipping keeps the
        # scatter in-table (garbage there is masked, then overwritten as
        # decode advances: the standard padded-prefill invariant)
        max_len = block_tables.shape[1] * page
        positions = jnp.clip(starts[:, None] + jnp.arange(s, dtype=jnp.int32),
                             0, max_len - 1).reshape(-1)
        seq_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
        return append_paged_kv(k_cache, v_cache, k_new.reshape(b * s, nkv, d),
                               v_new.reshape(b * s, nkv, d), block_tables,
                               positions, seq_ids)
    _pool_fold(k_cache, d)
    groups, w = k_cache.shape[1], k_cache.shape[3]
    n = s // page
    cols = starts[:, None] // page + jnp.arange(n, dtype=jnp.int32)  # [b, n]
    # past the table's width: a page out of range, whose update jax drops
    page_idx = jnp.take_along_axis(
        block_tables, cols, axis=1, mode="fill",
        fill_value=k_cache.shape[0]).reshape(-1)

    def blocks(x):
        # [b, s, kv_heads, d] is [b, n, page, groups, 128] as it stands (a
        # lane-dense row holds f heads side by side); a pool's page block
        # has the head group before the slot
        x = x.reshape(b, n, page, groups, w)
        return jnp.swapaxes(x, 2, 3).reshape(b * n, groups, page, w)

    with jax.named_scope("pt.kv_write"):
        return (k_cache.at[page_idx].set(blocks(k_new), mode="drop"),
                v_cache.at[page_idx].set(blocks(v_new), mode="drop"))


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


def gather_chain_pages(kv, blocks, head_dim=None):
    """Host-materialize a block chain's page bytes from every layer's
    (k, v) pool pair — the EXPORT half of KV-block migration
    (inference/disagg.py): ``kv`` is the engine's per-layer
    ``[(k_pages, v_pages), ...]`` list, ``blocks`` the chain's page ids in
    block-table order. Returns ``[(k_np, v_np), ...]`` with arrays of
    shape ``[len(blocks), kv_heads, page, head_dim]``: the logical order,
    whatever the pool's form (``head_dim``, the model's, un-folds the pages
    of a lane-dense pool; None takes the pool's trailing width), so an
    artifact is the same bytes from either form. The np.asarray
    readback fences any in-flight append/decode program that wrote these
    pages, so the bytes are exactly what the next decode step would have
    attended. int8 pools export their RAW int8 page bytes (the dequant
    scales travel separately — :func:`gather_chain_scales`), so the wire
    artifact's crc covers the quantized bytes exactly as stored."""
    import numpy as np

    require_kv_layers(kv, "the KV-chain export (gather_chain_pages)")
    idx = np.asarray(blocks, np.int32)
    out = []
    for k, v in kv:
        if isinstance(k, QuantizedKVPool):
            out.append((np.asarray(k.data[idx]), np.asarray(v.data[idx])))
        else:
            d = k.shape[-1] if head_dim is None else head_dim
            _pool_fold(k, d)
            out.append((unfold_kv_pages(np.asarray(k[idx]), d),
                        unfold_kv_pages(np.asarray(v[idx]), d)))
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
    header) alongside the raw int8 bytes. A lane-dense pool folds the
    logical pages it is handed (their trailing width is the head_dim).
    Returns the updated per-layer ``[(k_pages, v_pages), ...]`` list."""
    require_kv_layers(kv, "the KV-chain import (scatter_chain_pages)")
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
            f = _pool_fold(k, pk.shape[-1])
            out.append(tuple(
                pool.at[idx].set(fold_kv_pages(jnp.asarray(pg, pool.dtype), f))
                for pool, pg in ((k, pk), (v, pv))))
    return out


def gather_paged_kv(k_cache, v_cache, block_tables, max_len, head_dim=None):
    """Dense [b, max_len, hkv, d] views of the paged cache (prefill path /
    debugging; int8 pools come back dequantized fp32). max_len must be a
    multiple of page size. ``head_dim`` names d where the pool may be
    lane-dense (None: the pool's trailing width)."""
    b = block_tables.shape[0]
    page = k_cache.shape[2]
    d = k_cache.shape[3] if head_dim is None else head_dim
    hkv = k_cache.shape[1] * _pool_fold(k_cache, d)
    n = max_len // page
    tables = jnp.maximum(block_tables[:, :n], 0)
    kg = jnp.swapaxes(_gather_pages(k_cache, tables, d),
                      2, 3).reshape(b, max_len, hkv, d)
    vg = jnp.swapaxes(_gather_pages(v_cache, tables, d),
                      2, 3).reshape(b, max_len, hkv, d)
    return kg, vg
