"""Continuous-batching serving engine over paged KV caches.

The TPU-native counterpart of the reference's serving stack around
block_multihead_attention (python/paddle/incubate/nn/functional/
block_multihead_attention.py over block_multi_head_attention_kernel.cu)
plus its sampling op (python/paddle/tensor/search.py:1362 top_p_sampling):
a fixed pool of KV pages + per-slot block tables, requests admitted into
free slots as others finish — decode compute and cache memory are bounded
by the pool, not by the longest request.

Design (one jitted program per phase, static shapes):
  - ``max_batch`` slots share per-layer page pools sized
    ``max_batch * ceil(max_len / page)`` pages (``_init_paged_caches``).
  - ADMIT: a new request prefills ITS slot only. With ``prompt_buckets`` the
    prompt is right-padded to the nearest bucket (one compilation per bucket):
    the padded chunk fills the cache, then the last REAL token is re-stepped
    at its true position so the first sampled token sees exactly the real
    prompt — pad cache entries sit beyond the attended window and are
    overwritten as decode advances.
  - STEP: ONE fused ``lax.scan`` of ``paged_token_step`` advances EVERY
    active slot — per-row positions flow into the paged decode kernel;
    inactive slots run on a parked dummy row whose output is ignored.
    Without eos the schedule is deterministic, so the engine runs toward the
    next completion event per program (scan lengths block_size·2^k), chains
    the last-token carry device-to-device, and materializes token values
    LAZILY (``_drain_pending``) — zero synchronous host round-trips, like
    ``generate()``'s async dispatch. eos-carrying batches pace at
    ``block_size`` tokens per host sync (early exit needs the values).
  - SAMPLE: per-request temperature / top-p / top-k / seed, applied
    row-vectorized inside the fused step. Keys are stateless:
    ``fold_in(key(seed), token_position)`` — reproducible per request and
    independent of batching/arrival order. temperature==0 is greedy.
  - FINISH: eos or max_new_tokens frees the slot; its pages are reused by
    the next admission (tables are per-slot, so no copying). Tokens decoded
    past an eos inside a block are discarded on the host (bounded waste,
    the standard continuous-batching speculation tradeoff).

Numerics: with default greedy sampling the engine is EXACTLY equal to
``generate(cache_impl='paged')`` (verified token-for-token on the real chip);
versus the dense-cache generate it matches exactly in fp32 (CPU tests) while
bf16-on-TPU tokens may diverge at softmax near-ties between the two attention
kernels — the standard cross-kernel serving caveat.

**The step loop** (docs/SERVING.md "The mega-step"; one family at every
``max_batch`` since PR 30): block tables, per-slot positions, the
active-row mask and the sampling state are DEVICE-resident and mutated
only by traced scatter programs (``_queue_update`` -> ``_flush_updates``)
— no mutable host buffer is ever handed to ``jnp.asarray``, which retires
the async-borrow hazard class (PT-TRACE-005) at the source. Decode runs as
ONE jitted mega-step over all ``max_batch`` rows with ``jnp.where``-masked
inactive rows (admission or completion never changes the program shape),
sampling and the position advance stay in-graph, and prefill packs
multiple (slot, chunk) rows into one ``paged_prefill_chunk`` call
(``_run_pack``). Host bookkeeping is O(active): occupied slots live in a
dict, free slots in a deque. Greedy and seeded token streams equal
``generate()``'s on the CPU in fp32 — per-row values are independent of
batch width (the warm==cold argument, pinned by the stream tests).

**Speculative multi-token decoding** (``speculative=SpecConfig(...)`` —
docs/SERVING.md "Speculative decode"): each decode dispatch
emits 1..K+1 tokens per row — a device-resident n-gram drafter proposes K
tokens from a per-slot history ring, one K+1-wide ``paged_verify_step``
scores every position (``ops.paged_verify_attention`` append-then-gather),
and in-graph greedy exact-match acceptance keeps the longest correct
prefix plus one bonus token. Greedy output is byte-identical to the
non-speculative mega-step; blocks with a sampled row take that one.

**int8 KV block format** (``kv_cache=KVCacheConfig(dtype="int8")`` —
docs/SERVING.md "int8 KV cache"): pools become
``ops.paged_attention.QuantizedKVPool`` — int8 pages with per-(page, head)
absmax scales, quantize-on-append / dequantize-in-gather — halving (bf16)
to quartering (f32) pool bytes, and composing with COW, the radix prefix
cache and ``KVChainCodec`` migration (PTKV1 carries dtype + scales).

``prefix_cache=PrefixCacheConfig(...)`` switches admission to a radix
prefix cache over a refcounted block pool with chunked prefill
(docs/SERVING.md): prompts sharing a system-prompt/few-shot prefix map the
already-filled KV blocks into their table and only prefill the uncached
suffix, one ``prefill_chunk`` per step interleaved with the decode batch;
a full-prompt hit copy-on-writes its last block before the first-token
re-step. For any given prompt, warm and cold admissions emit bit-identical
token streams (greedy and seeded sampling — see
``paged_prefill_attention``). One scoping note: a cached chain's final
block holds position L-1 k/v written by the first-token re-step's decode
program, so a LONGER prompt extending that chain reads re-step k/v where
its own cold prefill would have run the chunk-prefill program — the values
are mathematically equal but may differ in the last ulp under bf16 on TPU.

**A page group a layer kind** (docs/SERVING.md "Window and full layers: a
page group a kind"): a model whose attention layers read different spans of
their history (``kv_groups()``: the full group, then a window a kind) gets a
pool, an allocator, a parking page and a device table a group from
``ops.paged_attention.PageGroups``, which the constructor, admission, the
packed prefill, the decode block and ``_release_slot`` ask; a window
group's pool does not depend on ``max_len``, a sequence maps its pages
ahead of each program and gives back those behind its window after it, and
a prefix hit needs both groups' cover. Every other model has the one group
and the programs it always had.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time as _time
import weakref
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..framework.compile_cache import first_call
from ..observability.tracing import program_span


# THE sampler lives in generation_utils so generate() and the engine share one
# implementation; re-exported here for the serving-facing API surface.
from ..models.generation_utils import (fold_keys as _fold_keys,
                                       sample_rows, validate_sampling)


@functools.partial(jax.named_call, name="pt.sampler")
def _greedy(logits):
    """The sampler's arm for all-greedy batches, under its scope."""
    return jnp.argmax(logits, -1).astype(jnp.int32)


# host-side page bookkeeping lives next to the paged kernels; re-exported
# here as the serving-facing API surface
from ..ops.paged_attention import (BlockAllocator, LayerStateError,
                                   PageGroups, RadixPrefixCache,
                                   chunk_kernel_layers, kernel_layers,
                                   layer_kinds,
                                   page_append_layers, pool_num_pages,
                                   state_bytes)

__all__ = ["AutoscaleConfig", "BlockAllocator", "BrownoutConfig",
           "ContinuousBatchingEngine", "EngineSaturated", "FleetConfig",
           "FleetRouter", "KVCacheConfig", "KVChainCodec", "KVChainCorrupt",
           "LayerStateError", "MeshConfig", "MeshDegraded",
           "PackOrderError", "PageAlignmentError", "PrefixCacheConfig",
           "RadixPrefixCache", "ReplicaState",
           "Request", "RequestJournal", "RequestShed", "SLOAutoscaler",
           "ServingSupervisor", "SpecConfig", "StepWatchdog", "TieredRouter"]


def __getattr__(name):
    # crash-recovery layer (recovery.py) re-exported lazily: it imports the
    # resilience stack, which must not load just because serving was
    # imported (same discipline as the faults/retry lazy imports below)
    if name in ("ServingSupervisor", "RequestJournal"):
        from . import recovery

        return getattr(recovery, name)
    if name in ("FleetRouter", "FleetConfig", "ReplicaState"):
        from . import fleet

        return getattr(fleet, name)
    if name in ("KVChainCodec", "KVChainCorrupt", "TieredRouter"):
        # disaggregated prefill/decode tiers (disagg.py) — lazy for the
        # same reason as the fleet: it pulls recovery + fleet in
        from . import disagg

        return getattr(disagg, name)
    if name in ("SLOAutoscaler", "AutoscaleConfig"):
        # the SLO-pressure autoscaler (autoscale.py) — lazy like the fleet:
        # importing serving must not pull the control loop in
        from . import autoscale

        return getattr(autoscale, name)
    if name == "StepWatchdog":
        from ..distributed.resilience.watchdog import StepWatchdog

        return StepWatchdog
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_CALLER = "starved_caller_s"
_PHASES = ("starved_emit_s", "starved_admit_s", "starved_prefill_s",
           "starved_dispatch_s", _CALLER)
# the pt.serve.* spans that own a phase; between a step's children (and in
# pt.serve.wait / call / build, which own none) the enclosing phase goes on,
# and step() itself starts in the dispatch phase
_PHASE_OF = {"serve.emit": "starved_emit_s", "serve.admit": "starved_admit_s",
             "serve.prefill": "starved_prefill_s",
             "serve.decode.dispatch": "starved_dispatch_s"}
_LONG_S = 1.0       # a step, or a caller's interval, past this is a stall


class _InFlight:
    """The engine's ledger of what it has in flight on the device.

    Every program call takes the next number (``called``); a value the host
    later blocks on remembers the number of the call that made it. One chip
    runs its programs in order, so when the read of call ``n`` returns,
    every call up to ``n`` has finished (``done``), and when
    ``done == called`` the device is EMPTY: the engine stamps that instant
    (``stats["drains"]``). From the stamp to the start of the next call the
    device is *starved*: that time is added, split at the boundaries of the
    ``pt.serve.*`` spans, to the one of ``_PHASES`` the host was in, and
    ``device_starved_s`` is kept as their sum. A wait on an older value
    stamps nothing, and on the path without an eos id nothing is read back,
    so the counters stand still there.

    The blind spot as a number: a call whose predecessor's output is
    already there (``is_ready()``, no blocking) with no drain stamped since
    adds the time since that predecessor's call to
    ``device_maybe_starved_s``: the device was empty for an unknown part of
    it. ``device_starved_s`` is a floor of the idle time the host is at
    fault for, and the sum of the two a ceiling.

    An interval outside ``step()`` longer than ``_LONG_S`` (a profiler
    writing its trace out, a caller that went away) goes to
    ``caller_over_1s`` / ``caller_over_1s_s`` and into neither. An engine
    left without work is not starved: the stamp is dropped. Every time
    handed in is ``time.perf_counter``'s; only program calls the engine
    makes are seen, on one chip (docs/OBSERVABILITY.md "What the device
    waits for")."""

    __slots__ = ("stats", "called", "done", "mark", "phase", "out",
                 "out_at", "left_at")

    def __init__(self, stats: dict):
        self.stats = stats
        self.called = self.done = 0
        self.mark = None        # since when the device is known empty
        self.phase = _CALLER
        self.out = None         # the newest call's output, and its time
        self.out_at = 0.0
        self.left_at = None     # when step() last returned with work left
        stats.update(dict.fromkeys(_PHASES, 0.0), device_starved_s=0.0,
                     device_maybe_starved_s=0.0, drains=0,
                     caller_over_1s=0, caller_over_1s_s=0.0)

    def upto(self, now: float):
        """Bring the starved counters up to ``now``."""
        if self.mark is None:
            return
        st = self.stats
        st[self.phase] += now - self.mark
        self.mark = now
        st["device_starved_s"] = (
            st["starved_emit_s"] + st["starved_admit_s"]
            + st["starved_prefill_s"] + st["starved_dispatch_s"]
            + st[_CALLER])

    def enter(self, phase: str, now: float) -> str:
        """The host passes into ``phase`` at ``now``; returns the one it
        left."""
        self.upto(now)
        left, self.phase = self.phase, phase
        return left

    def call(self, now: float):
        """A program call starts at ``now``."""
        if self.mark is not None:
            self.upto(now)
            self.mark = None
        elif self.out is not None:
            # one execution makes all of a call's outputs: any leaf says
            leaf = self.out
            while isinstance(leaf, (tuple, list)) and leaf:
                leaf = leaf[0]
            if not hasattr(leaf, "is_ready"):
                leaf = next(iter(jax.tree_util.tree_leaves(leaf)), None)
            if (hasattr(leaf, "is_ready") and not leaf.is_deleted()
                    and leaf.is_ready()):
                self.stats["device_maybe_starved_s"] += now - self.out_at
        self.called += 1
        self.out, self.out_at = None, now

    def read(self, seq: int, now: float):
        """The host's read of a value of call ``seq`` returned at ``now``."""
        if seq > self.done:
            self.done = seq
        if self.done == self.called and self.mark is None:
            self.mark = now
            self.stats["drains"] += 1

    def step_begins(self, now: float):
        if self.left_at is None:
            # the engine had no work: what it called since (finished()'s
            # releases) and how long ago says nothing of a starved device
            self.mark = self.out = None
        elif now - self.left_at > _LONG_S:
            self.stats["caller_over_1s"] += 1
            self.stats["caller_over_1s_s"] += now - self.left_at
            if self.mark is not None:
                self.mark = now
            # what of that interval lies after the newest call (finished()
            # may have called inside it) is not maybe-starved time either
            self.out_at += now - max(self.out_at, self.left_at)
        self.enter("starved_dispatch_s", now)

    def step_ends(self, now: float, has_work: bool):
        self.enter(_CALLER, now)
        self.left_at = now if has_work else None


class _phase_span(program_span):
    """A ``pt.serve.*`` span that owns a phase of the in-flight ledger."""

    __slots__ = ("_flight", "_phase")

    def __init__(self, engine, name: str, **args):
        super().__init__(name, engine.tracer, engine.trace_tags, **args)
        self._flight, self._phase = engine._flight, _PHASE_OF[name]

    def __enter__(self):
        super().__enter__()
        self._phase = self._flight.enter(self._phase, self._t0)
        return self

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._flight.enter(self._phase, self._t0 + self.elapsed_s)
        return False


class _call_span(program_span):
    """``pt.serve.call``: one call of a jitted program, a leaf. ``seq`` is
    the call's number in the in-flight ledger, ``drained`` whether the
    device was known empty when it started."""

    __slots__ = ("_flight",)

    def __init__(self, engine, program: str, key: str):
        fl = self._flight = engine._flight
        super().__init__("serve.call", engine.tracer, engine.trace_tags,
                         program=program, seq=fl.called + 1,
                         drained=int(fl.mark is not None), key=key)

    def __enter__(self):
        super().__enter__()
        self._flight.call(self._t0)
        return self


class _wait_span(program_span):
    """``pt.serve.wait``: the host blocks on a device value of call ``seq``.
    Its wall time is the engine's ``stats["device_wait_s"]``; its end tells
    the in-flight ledger that every call up to ``seq`` has finished."""

    __slots__ = ("_stats", "_flight", "_seq")

    def __init__(self, engine, what: str, seq: int):
        super().__init__("serve.wait", engine.tracer, engine.trace_tags,
                         what=what, seq=seq)
        self._stats, self._flight, self._seq = (engine.stats, engine._flight,
                                                seq)

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._stats["device_wait_s"] += self.elapsed_s
        self._flight.read(self._seq, self._t0 + self.elapsed_s)
        return False


class MeshDegraded(RuntimeError):
    """PT-SRV-008: the engine's tp device group lost devices mid-serve
    (the seeded ``device.loss`` fault site, or a real runtime device
    failure surfaced by the caller). Carries ``lost`` (devices gone) and
    ``survivors`` (devices still usable); the elastic
    :class:`ServingSupervisor` catches it, reshards the engine to the
    widest surviving tp width that still divides both head counts
    (falling to unsharded when none does), and re-admits every
    unfinished request from the journal byte-identically
    (docs/RESILIENCE.md "Elastic serving mesh")."""

    def __init__(self, msg: str, lost: int = 0, survivors: int = 1):
        super().__init__(msg)
        self.lost = int(lost)
        self.survivors = max(0, int(survivors))


class PageAlignmentError(ValueError):
    """PT-SRV-010: a packed-prefill row's offset is no multiple of the page.
    ``jit_pt_prefill_chunk`` writes K and V by the page
    (``ops.paged_attention.append_paged_chunk``), which is right only for
    rows that start on a page; admission and ``_run_pack`` keep every offset
    there (a prefix hit's whole pages, then whole chunks), so this names a
    bug in the engine, before it can write a page's tokens into the wrong
    slots."""


class PackOrderError(ValueError):
    """PT-SRV-011: the rows of a pack for a model whose layers keep a state
    a sequence (kind ``"seq"``) are not by sequence: the chunks of one slot
    adjacent, each starting where the one before it ended.
    ``ops.ssd.ssd_scan_pooled`` reads a slot's state once a run of adjacent
    rows and writes it back once, so a slot in two runs would resume both
    from the same state and keep one of them; it cannot raise on a traced
    value, and ``_run_pack`` orders the rows itself, so this names a bug in
    the engine, before the call."""


class EngineSaturated(RuntimeError):
    """add_request refused: the engine's wait queue is at its high-water
    mark (``max_queue``). Admission control — callers shed load, retry with
    backoff, or scale out; the engine never hides an unbounded backlog."""


class RequestShed(RuntimeError):
    """add_request refused at SUBMIT time (PT-SRV-003): the request's
    ``deadline_s`` cannot be met at the engine's current decode throughput,
    so admitting it would only let it time out after queuing — wasting queue
    capacity and deadline-eviction work while helping nobody. Shedding
    happens before the request touches any engine state, so concurrently
    running requests' token streams are byte-identical to a run without the
    shed request. Callers route to another replica or degrade gracefully."""


@dataclasses.dataclass
class PrefixCacheConfig:
    """Knobs for the paged-KV prefix cache + chunked prefill
    (``ContinuousBatchingEngine(prefix_cache=...)`` — docs/SERVING.md).

    - ``prefill_chunk``: tokens prefilled per engine step per admitted slot
      (rounded up to a page multiple; default ``min(max_len, 8 * page)``).
      Long prompts advance one chunk per step INTERLEAVED with the decode
      batch, so a 2k-token admit no longer stalls every decoding slot.
    - ``extra_blocks``: pool headroom beyond the ``max_batch *
      pages_per_seq`` working set, retained for cached prefixes (0 still
      caches — prefix SHARING itself frees blocks).
    - ``pack_rows``: prompt-packing budget — max (slot, chunk)
      rows per packed prefill call (default ``max(8, min(max_batch, 32))``;
      the pack always covers at least one chunk per mid-prefill slot, so
      this only bounds the EXTRA rows that let short prompts finish in one
      call). With a window page group (docs/SERVING.md "Window and full
      layers") one slot takes ``PageGroups.slot_rows`` of them at most: its
      window pages are mapped that far ahead and no further."""

    prefill_chunk: Optional[int] = None
    extra_blocks: int = 0
    pack_rows: Optional[int] = None


@dataclasses.dataclass
class SpecConfig:
    """Knobs for speculative multi-token decoding inside the
    mega-step (``ContinuousBatchingEngine(speculative=...)`` —
    docs/SERVING.md "Speculative decode").

    - ``k``: draft tokens proposed (and verified) per dispatch — each spec
      dispatch can emit 1..k+1 tokens per row (accepted prefix + one bonus
      from the verify logits).
    - ``ngram``: match length of the device-resident prompt-lookup
      drafter — the row's last ``ngram`` tokens are searched in its
      history ring; the continuation after the most recent match becomes
      the draft.
    - ``history``: per-slot device ring-buffer length (tokens) the drafter
      searches — generated + prompt ids, seeded from the prompt at
      activation.
    - ``_unsafe_accept_all``: DRILL-ONLY (tools/fault_drill.py
      ``spec_decode_divergence`` control arm): skip the argmax
      verification and trust every draft — demonstrates the silent greedy
      divergence the in-graph verify exists to prevent. Never enable.

    Greedy (temperature==0) output is byte-identical to the
    non-speculative mega-step — drafts only change how many tokens a
    dispatch emits, never which tokens. Blocks containing sampling rows
    (temperature>0) keep the sampled mega-step.

    Composition with ``KVCacheConfig(dtype="int8")``: rejected drafts'
    appends feed the int8 blocks' monotone absmax scales, so a spec+int8
    engine's streams may differ from a NON-spec int8 engine's in the last
    quantization bit (int8 is lossy either way). What still holds — and
    is pinned by tests — is full determinism: identical spec+int8
    engines, warm/cold re-admissions and crash replay reproduce the same
    bytes (drafts are a deterministic function of the stream, so so is
    the rejected-append garbage)."""

    k: int = 4
    ngram: int = 2
    history: int = 64
    _unsafe_accept_all: bool = False


@dataclasses.dataclass
class KVCacheConfig:
    """Paged-KV pool storage format
    (``ContinuousBatchingEngine(kv_cache=...)`` — docs/SERVING.md "int8 KV
    cache"). ``dtype="int8"`` switches every pool to the int8 block
    format (``ops.paged_attention.QuantizedKVPool``): int8 pages with
    per-(page, head) absmax scales, quantize-on-append /
    dequantize-in-gather — pool bytes drop ~itemsize-fold (bf16 halves),
    doubling effective slots and radix prefix-cache reach at equal memory.
    Composes with COW (scales copy with the page), the radix prefix cache,
    and ``KVChainCodec`` migration (the PTKV1 artifact carries dtype +
    scales, crc over the int8 bytes)."""

    dtype: Optional[str] = None

    def __post_init__(self):
        if self.dtype not in (None, "param", "int8"):
            raise ValueError(f"unsupported KV cache dtype {self.dtype!r} "
                             "(supported: None/'param', 'int8')")


@dataclasses.dataclass
class MeshConfig:
    """Mesh-sharded serving (``ContinuousBatchingEngine(mesh=...)`` —
    docs/SERVING.md "Sharded serving").

    ``tp`` devices run every hot-path program (mega-step, packed
    prefill chunk, speculative verify, first-token re-step) under
    ``shard_map``: weights are column-sharded along their OUTPUT dim
    (q/k/v along heads, gate/up along mlp, an untied lm_head along
    vocab), the paged KV pools shard along kv_heads to match the k/v
    projections, and the only collectives are ``all_gather``s of
    DISJOINT shards — pure data movement. Every output element is
    computed whole on exactly one device with its contraction in the
    original order, so greedy streams are byte-identical to the
    1-device engine at any ``tp`` (the serving identity contract; a
    psum-style partial-sum reduction would reassociate and is
    impossible by construction in this layout). In-replica ``tp``
    composes with procfleet scale-out: each worker binds its own device
    group (``ProcFleetConfig.mesh``).

    - ``tp``: tensor-parallel width (devices per engine replica).
    - ``devices``: explicit device list (length >= tp; default
      ``jax.devices()[:tp]``) — procfleet workers pass their group.
    - ``abstract``: build a symbolic ``jax.sharding.AbstractMesh``
      instead of binding real devices — tracing/audit only (PT-COMM /
      PT-COST record the sharded programs' contracts on a 1-device
      host this way); actually dispatching on an abstract engine fails
      by construction.

    Requires a prefix cache, and a model that
    opts in via the ``tp_serving = True`` marker (llama; GPT's fused
    interleaved qkv projection cannot be column-sharded)."""

    tp: int = 1
    devices: Optional[Sequence] = None
    abstract: bool = False

    def __post_init__(self):
        if int(self.tp) < 1:
            raise ValueError(f"MeshConfig.tp must be >= 1, got {self.tp}")


def ngram_draft(hist, hlen, last_tok, k: int, n: int):
    """Device-resident prompt-lookup drafter (no draft model, no host
    sync): propose ``k`` draft tokens per row from its history ring.

    ``hist`` [B, H] int32 ring buffer of emitted tokens (token with global
    index g lives at slot g % H), ``hlen`` [B] tokens written so far,
    ``last_tok`` [B] the newest token (not yet in the ring — it enters on
    the next spec step, so the effective sequence is
    ``hist-window ++ last_tok``). The row's last ``n`` tokens are matched
    against every earlier window; the ``k`` tokens following the MOST
    RECENT match become the draft. No match (or under ``n`` tokens of
    history) falls back to repeating ``last_tok`` — drafts never affect
    WHICH tokens are emitted (greedy verify is exact), only how many per
    dispatch, so the fallback costs acceptance, never correctness."""
    H = hist.shape[1]
    g = hlen[:, None] - H + jnp.arange(H)[None, :]      # global idx per slot
    lin = jnp.take_along_axis(hist, jnp.mod(g, H), axis=1)
    lin = jnp.concatenate([lin, last_tok[:, None]], axis=1)     # [B, H+1]
    L = H + 1
    tail = lin[:, L - n:]                               # the current n-gram
    J = L - n                                           # candidate starts
    win_idx = jnp.arange(J)[:, None] + jnp.arange(n)[None, :]
    wins = lin[:, win_idx]                              # [B, J, n]
    match = jnp.all(wins == tail[:, None, :], axis=-1)  # [B, J]
    valid = (g >= 0)[:, :J]          # J = L - n <= H: start-slot validity
    jv = jnp.where(match & valid, jnp.arange(J)[None, :], -1)
    jbest = jnp.max(jv, axis=1)
    has = (jbest >= 0) & (hlen >= n)
    cont = jnp.clip(jbest[:, None] + n + jnp.arange(k)[None, :], 0, L - 1)
    drafts = jnp.take_along_axis(lin, cont, axis=1)
    return jnp.where(has[:, None], drafts,
                     last_tok[:, None]).astype(jnp.int32)


def spec_accept(drafts, targets, caps):
    """Pure accept/reject math of greedy speculative decoding (in-graph;
    host-testable without a model — tests/test_serving_spec.py).

    ``drafts`` [B, K] proposed tokens, ``targets`` [B, K+1] the greedy
    (argmax) token per verify-window position — ``targets[:, i]`` is what
    the model emits AFTER window position i, so draft i is correct iff
    ``drafts[:, i] == targets[:, i]`` and every earlier draft was.
    ``caps`` [B] >= 0 bounds per-row emission (max_new / max_len budget;
    0 masks a row out entirely).

    Returns ``(out [B, K+1], emit [B], n_acc [B])``: the emitted tokens
    are ``out[:, :emit]`` — the accepted draft prefix plus ONE bonus token
    (the model's own next token after the last accepted position), which
    is exactly the non-speculative greedy stream."""
    B, K = drafts.shape
    match = drafts == targets[:, :K]
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
    n_acc = jnp.sum(acc, axis=1)                        # [B] 0..K
    emit = jnp.minimum(n_acc + 1, jnp.maximum(caps, 0))
    bonus = jnp.take_along_axis(targets, n_acc[:, None], axis=1)
    padded = jnp.concatenate([drafts, bonus], axis=1)   # [B, K+1]
    out = jnp.where(jnp.arange(K + 1)[None, :] < n_acc[:, None],
                    padded, bonus)
    return (out.astype(jnp.int32), emit.astype(jnp.int32),
            n_acc.astype(jnp.int32))


@dataclasses.dataclass
class BrownoutConfig:
    """Hysteretic degraded mode under sustained KV-pool pressure
    (``ContinuousBatchingEngine(brownout=...)`` — docs/SERVING.md).

    After ``enter_after`` consecutive steps with a deferred admission (the
    pool could not serve the queue head even after LRU eviction) the engine
    enters **brownout**: idle cached blocks are flushed back to the pool,
    prefix-cache admission stops matching/registering chains, and chunked
    prefill collapses to whole-prompt prefill — the byte-identical legacy
    serving behavior (warm==cold bit-identity means token streams cannot
    change, only memory/throughput shape). Brownout exits only after
    ``exit_after`` consecutive pressure-free steps with at least
    ``exit_free_frac`` of the pool free — hysteresis, so a workload
    oscillating at the edge does not flap the cache on and off."""

    enter_after: int = 2
    exit_free_frac: float = 0.5
    exit_after: int = 4


class Request:
    """One generation request tracked by the engine.

    Sampling params mirror ``generate()``: ``temperature=0`` (default) is
    greedy; otherwise temperature + optional top-p (nucleus) + top-k filter.
    ``seed`` (default: the request id) makes the request's sample stream
    reproducible regardless of batching or arrival order.

    ``deadline_s`` (measured from enqueue) bounds the request's total life
    — queue wait plus decode. A request past its deadline is evicted at the
    next engine step: ``done=True, failed=True``, ``error`` names the
    deadline, its slot/pages are freed, and other slots are untouched.
    Eviction latency is bounded by one decode block. A deadline the engine
    can already see is infeasible at submit time is refused with
    :class:`RequestShed` instead of queuing (PT-SRV-003).

    ``priority`` orders admission: lower values admit first (0 = highest);
    within a class, arrival order (FIFO) is preserved. Priorities reorder
    the WAIT QUEUE only — already-admitted slots are never preempted, so a
    late high-priority burst shortens queue wait without corrupting anyone's
    stream.
    """

    PRIORITY_HIGH = 0
    PRIORITY_NORMAL = 1
    PRIORITY_LOW = 2

    _counter = [0]

    def __init__(self, prompt_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_p: float = 1.0,
                 top_k: int = 0, seed: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 priority: int = PRIORITY_NORMAL,
                 tenant: Optional[str] = None):
        validate_sampling(temperature, top_p, top_k)
        Request._counter[0] += 1
        self.rid = Request._counter[0]
        self.prompt = np.asarray(
            prompt_ids._data if isinstance(prompt_ids, Tensor) else prompt_ids
        ).reshape(-1).astype(np.int32)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.top_k = int(top_k)
        self.seed = int(seed if seed is not None else self.rid)
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.priority = int(priority)
        # workload tenant tag (observability/workload.py multi-tenant mix):
        # rides the trace stamps so SLO attainment splits per tenant
        # (observability/slo.py); journaled, so it survives failover
        self.tenant = None if tenant is None else str(tenant)
        self.output: List[int] = []
        self.done = False
        self.failed = False
        self.error: Optional[str] = None
        self._enqueued_at: Optional[float] = None  # set by add_request
        # tokens SCHEDULED so far (device-side results may still be pending
        # materialization — without eos the schedule is deterministic, so the
        # engine books progress before reading any token value)
        self._n_out = 0
        self._engine = None  # weakref, set by add_request

    @property
    def tokens(self) -> List[int]:
        """Materialized output tokens. Under async (deterministic-schedule)
        batching, ``done`` can flip True while token blocks are still
        device-side; this accessor drains the engine's pending readbacks
        first, so it is always complete once ``done`` is True. Reading
        ``.output`` directly is only guaranteed complete after the engine's
        ``finished()`` has returned the request."""
        eng = self._engine() if self._engine is not None else None
        if eng is not None:
            eng._drain_pending()
        elif len(self.output) < self._n_out:
            raise RuntimeError(
                f"request {self.rid}: {self._n_out - len(self.output)} "
                "scheduled tokens were never materialized and the engine has "
                "been garbage-collected — keep the engine alive (or call its "
                "finished()) before dropping it")
        return self.output


class ContinuousBatchingEngine:
    # Carry/donation declaration for the jitted hot-path programs —
    # consumed by the jit builders below and pinned by tests
    # (test_program_cost.py: every declared carry must be donated, and
    # non-carries never); tools/audit_program_cost.py then audits the
    # resulting ``donated_invars`` off the TRACED programs (PT-COST-003).
    # The kv pools / device position vector are step-to-step carries;
    # donating them lets XLA alias the output buffers in place of keeping
    # two copies of the KV pool live across every decode block.
    # ``tables`` / ``act`` / the sampling vectors are NOT carries of these
    # programs (the mega-step returns neither) and must stay undonated.
    # Argnums index the builders' positional args.
    _MEGA_ARG_NAMES = ("params", "toks", "kv", "tables", "pos", "act",
                       "seeds", "temps", "tops", "topks")
    _MEGA_CARRIES = ("kv", "pos")
    _MEGA_DONATE_ARGNUMS = (2, 4)
    _CHUNK_ARG_NAMES = ("params", "ids", "kv", "rows", "starts")
    _CHUNK_CARRIES = ("kv",)
    _CHUNK_DONATE_ARGNUMS = (2,)
    # first-token program: kv is the carry worth donating (the full KV
    # pool); ``last_tok`` is also a carry but is max_batch int32s —
    # deliberately left undonated (not worth the aliasing constraint)
    _FIRST_ARG_NAMES = ("params", "last", "kv", "rows", "last_tok",
                        "ints", "floats")
    _FIRST_CARRIES = ("kv",)
    _FIRST_DONATE_ARGNUMS = (2,)
    # speculative verify mega-step (docs/SERVING.md "Speculative decode"):
    # kv pools, positions and the drafter's history ring/length are all
    # step-to-step carries; tables/act/caps are read-only inputs the host
    # keeps live across the call and must stay undonated.
    _SPEC_ARG_NAMES = ("params", "toks", "kv", "tables", "pos", "act",
                       "hist", "hlen", "caps")
    _SPEC_CARRIES = ("kv", "pos", "hist", "hlen")
    _SPEC_DONATE_ARGNUMS = (2, 4, 6, 7)

    def __init__(self, model, max_batch: int = 8, max_len: int = 512,
                 page_size: int = 64, block_size: int = 8,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 max_queue: Optional[int] = None,
                 prefix_cache: Union[bool, PrefixCacheConfig, None] = False,
                 compile_cache_cap: int = 64,
                 shed_infeasible: bool = True,
                 brownout: Union[bool, BrownoutConfig, None] = None,
                 fused: Optional[bool] = None,
                 speculative: Union[bool, SpecConfig, None] = None,
                 kv_cache: Union[str, KVCacheConfig, None] = None,
                 mesh: Union[int, "MeshConfig", None] = None,
                 tracer=None, trace_tags: Optional[Dict] = None):
        # ``fused`` chooses nothing since PR 30: there is one family of step
        # programs. The name stays only because chipbench's cell files hand
        # ``"fused": true`` over as a keyword; a ``benchmark`` issue drops
        # the key there and the parameter here.
        if fused not in (None, True):
            raise ValueError(
                "fused=False: the legacy step programs left the engine in "
                "PR 30 — there is one decode family at every max_batch; "
                "drop the argument")
        self.model = model
        # per-request trace spans (observability.TraceRecorder — docs/
        # OBSERVABILITY.md): every stamp site is host-side, behind a single
        # `is not None` check, and records into a bounded buffer — nothing
        # on the jitted step path. Assignable post-construction (the
        # ServingSupervisor attaches one to factory-built engines).
        self.tracer = tracer
        self.trace_tags = dict(trace_tags or {})
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.block_size = max(1, int(block_size))
        # bounded-queue backpressure: add_request raises EngineSaturated
        # past this many waiting requests (None = unbounded, legacy)
        self.max_queue = None if max_queue is None else max(0, int(max_queue))
        self.prompt_buckets = (sorted(int(b) for b in prompt_buckets)
                               if prompt_buckets else None)
        if self.prompt_buckets and self.prompt_buckets[-1] > max_len:
            raise ValueError(f"prompt bucket {self.prompt_buckets[-1]} "
                             f"exceeds max_len {max_len}")
        self.compile_cache_cap = max(1, int(compile_cache_cap))
        if prefix_cache is True:
            prefix_cache = PrefixCacheConfig()
        elif not prefix_cache:
            prefix_cache = None
        self.prefix_cache = prefix_cache
        # deadline-feasibility shedding (PT-SRV-003): armed once the engine
        # has measured a decode rate; until then every deadline is admitted
        # (a cold engine has no basis to refuse work)
        self.shed_infeasible = bool(shed_infeasible)
        if brownout is True:
            brownout = BrownoutConfig()
        elif not brownout:
            brownout = None
        self._brownout_cfg = brownout if prefix_cache is not None else None
        self._brownout_active = False
        self._pressure_steps = 0
        self._clear_steps = 0
        self._deferred_step = False
        self._step_idx = 0
        # EMA of scheduled-tokens/s across engine steps — the denominator of
        # the feasibility estimate (updated only on steps that scheduled
        # tokens, so idle ticks don't decay it toward zero)
        self._ema_tok_s: Optional[float] = None
        self._sched_tokens = 0
        self._maxp = -(-max_len // page_size)
        # speculative multi-token decoding (docs/SERVING.md "Speculative
        # decode"): a device-resident n-gram drafter + one K-wide verify
        # program per dispatch, greedy-exact — a mega-step variant over the
        # device-resident state.
        if speculative is True:
            speculative = SpecConfig()
        elif not speculative:
            speculative = None
        self._spec = speculative
        if self._spec is not None:
            if self._spec.k < 1 or self._spec.ngram < 1:
                raise ValueError("SpecConfig.k and .ngram must be >= 1")
            if self._spec.history < self._spec.ngram + self._spec.k:
                raise ValueError(
                    f"SpecConfig.history {self._spec.history} too short for "
                    f"ngram {self._spec.ngram} + k {self._spec.k}")
        # opt-in int8 paged-KV block format (docs/SERVING.md "int8 KV
        # cache"): pools become QuantizedKVPool (int8 pages + per-block
        # absmax scales) — every engine program and the migration codec
        # handle the format transparently.
        if isinstance(kv_cache, str):
            kv_cache = KVCacheConfig(dtype=kv_cache)
        elif kv_cache is None:
            kv_cache = KVCacheConfig()
        self.kv_cache = kv_cache
        self._kv_dtype = kv_cache.dtype if kv_cache.dtype == "int8" else None
        # mesh-sharded serving (docs/SERVING.md "Sharded serving"): every
        # hot-path program becomes jit(shard_map(...)) over a tp axis with
        # column-parallel weights and kv_heads-sharded pools. The gathers
        # concatenate disjoint shards — no reduction ever crosses a shard
        # boundary — so greedy streams stay byte-identical to the 1-device
        # engine (param specs + placement happen at the end of the ctor,
        # once the param list exists).
        if isinstance(mesh, int):
            mesh = MeshConfig(tp=mesh)
        self.mesh = mesh
        self._mesh = None
        self._mesh_axis = None
        if mesh is not None:
            self._refuse_over_groups(("a tp mesh", True))
            if prefix_cache is None:
                raise ValueError(
                    "mesh-sharded serving needs a prefix cache "
                    "(prefix_cache=...) — the bucketed prefill program of "
                    "the static pool layout stays single-device")
            if not getattr(model, "tp_serving", False):
                raise ValueError(
                    f"{type(model).__name__} does not support tensor-"
                    "parallel serving (no tp_serving marker): its weights "
                    "must be column-shardable along heads/mlp/vocab")
            self._mesh_axis = "tp"
            tp = int(mesh.tp)
            if mesh.abstract:
                from ..static.comm.mesh import abstract_mesh

                self._mesh = abstract_mesh({self._mesh_axis: tp})
            else:
                devs = (list(mesh.devices) if mesh.devices is not None
                        else jax.devices()[:tp])
                if len(devs) < tp:
                    raise ValueError(
                        f"MeshConfig.tp={tp} needs {tp} devices, got "
                        f"{len(devs)} — on CPU hosts raise "
                        "--xla_force_host_platform_device_count")
                self._mesh = jax.sharding.Mesh(np.asarray(devs[:tp]),
                                               (self._mesh_axis,))
        if prefix_cache is not None:
            c = prefix_cache.prefill_chunk or min(max_len, 8 * page_size)
            self._chunk_tokens = -(-int(c) // page_size) * page_size
            # one page group a layer kind that keeps K and V (PageGroups);
            # a model that declares none has the one full group, sized
            # max_batch * pages a sequence + extra_blocks as ever
            self._pack_rows = (max(8, min(max_batch, 32))
                               if prefix_cache.pack_rows is None
                               else max(1, int(prefix_cache.pack_rows)))
            self._groups = PageGroups(
                getattr(model, "kv_groups", lambda: [("full", None)])(),
                max_batch=max_batch, max_len=max_len, page_size=page_size,
                chunk=self._chunk_tokens, block=self.block_size,
                extra_blocks=prefix_cache.extra_blocks)
            self._refuse_over_groups(
                ("speculative decoding", self._spec is not None),
                ("an int8 KV pool", self._kv_dtype == "int8"))
            n_blocks = self._groups.full.num_blocks
            # +1 page: parked decode rows (free / still-prefilling slots)
            # write their dummy token into a dedicated parking page, never
            # into a block another request may share. The pools come back
            # with that count rounded up to the tile's rows
            # (ops.paged_attention.pool_pages); the spare pages lie after
            # the parking page and the allocator never sees them
            # a tp mesh cuts the pools along their KV heads: a lane-dense
            # pool has to fold each shard's own heads
            self.caches = model._init_paged_caches(
                max_batch, max_len, page_size, num_blocks=n_blocks + 1,
                kv_dtype=self._kv_dtype,
                kv_shards=1 if mesh is None else int(mesh.tp),
                **({} if self._groups.single else
                   {"group_blocks": self._groups.pool_pages()}))
            # the FULL group's: what every engine had before there were
            # groups, and what the fault drills and the benchmark's
            # kv_pool_used_share read
            self._park = self._groups.full.park
            self._alloc = self._groups.full.alloc
            self._radix = self._groups.radix
            # the device tables (one a group; one array with one group)
            # start all-parked; only _flush_updates' scatters write them
            # afterwards (no host table exists)
            self.caches = {"kv": self.caches["kv"],
                           "tables": jax.tree_util.tree_map(
                               jnp.asarray,
                               self._groups.parked(max_batch))}
            self._slot_rows: List[Optional[np.ndarray]] = [None] * max_batch
            self._slot_blocks: List[Optional[List[int]]] = [None] * max_batch
            self._prefill_next: Dict[int, int] = {}
            self._jit_chunk: Dict[int, object] = {}
            self._jit_first: Dict[tuple, object] = {}
            self._jit_cow_batch: Dict[int, object] = {}
        else:
            self._groups = None
            self._refuse_over_groups(
                ("an engine without a prefix cache (slot-owned pages of one "
                 "size)", True))
            self.caches = model._init_paged_caches(max_batch, max_len,
                                                   page_size,
                                                   kv_dtype=self._kv_dtype)
        # what each layer keeps, as the model's caches say it: "kv" (pages of
        # K and V a token) or "state" (a fixed block kept with the page:
        # ops.paged_attention.PageState — docs/SERVING.md "State that is
        # not pages"). A state ring rides every program inside
        # caches["kv"], is shared, copied on write, evicted and migrated
        # with its page, and needs from the engine only each chunk row's
        # count of real tokens (a padded tail must leave no trace in it).
        # "seq" is a state kept a SEQUENCE (ops.paged_attention.SeqState: a
        # row a slot, too large to ride the pages). It rides the programs
        # inside caches["kv"] too; the engine tells the model which slot
        # each row of a program is, keeps every chunk row's state short of
        # the prompt's last token (the first-token program steps that one),
        # and leaves the radix trie alone: no page carries the state a hit
        # would have to resume from.
        kinds = layer_kinds(self.caches["kv"])
        self._state_layers = [i for i, k in enumerate(kinds) if k == "state"]
        self._seq_layers = [i for i, k in enumerate(kinds) if k == "seq"]
        for what, on in (
                ("speculative decoding (a rejected draft cannot be taken "
                 "back out of the state)", self._spec is not None),
                ("an engine without a prefix cache (bucketed prompts are "
                 "prefilled through generate()'s dense-cache hook)",
                 prefix_cache is None)):
            for kind, layers, held in (
                    ("state", self._state_layers, "PageState"),
                    ("seq", self._seq_layers, "SeqState")):
                if on and layers:
                    raise LayerStateError(
                        f"PT-SRV-009: {type(model).__name__} keeps layers "
                        f"{layers} of kind {kind!r} ({held}); they cannot "
                        f"be served by {what}")
        self._ctr_layout: List[tuple] = []
        self._slots: List[Optional[Request]] = [None] * max_batch
        # O(active) bookkeeping (big-batch refactor): occupied slots in a
        # dict, free slots in a deque — per-step work is bounded by what is
        # actually live, never by max_batch (a 256-slot engine pays those
        # scans per token otherwise). ``_slots`` stays the authoritative
        # slot array; these are maintained at the same chokepoints.
        self._occupied: Dict[int, Request] = {}
        self._free_slots: collections.deque = collections.deque(
            range(max_batch))
        # per-slot NEXT write position (== tokens currently in the slot's cache)
        self._pos = np.zeros(max_batch, np.int32)
        # last emitted token per slot, DEVICE-resident: the decode chain never
        # round-trips token values through the host (they're materialized
        # lazily from self._pending — see _drain_pending)
        self._last_tok = jnp.zeros(max_batch, jnp.int32)
        self._pending: List[tuple] = []
        # device-resident per-slot step state: positions, active mask,
        # sampling params (seeds, temperatures, top_p, top_k).
        # Admission/release mutate them ONLY through _queue_update ->
        # _flush_updates (traced scatters applied at the next decode
        # dispatch) — no mutable host buffer is ever handed to
        # jnp.asarray, which retires the async-borrow hazard class
        # (PT-TRACE-005) at the source.
        self._dev_pos = jnp.zeros(max_batch, jnp.int32)
        self._dev_act = jnp.zeros(max_batch, jnp.bool_)
        self._dev_samp = (jnp.zeros(max_batch, jnp.int32),
                          jnp.zeros(max_batch, jnp.float32),
                          jnp.ones(max_batch, jnp.float32),
                          jnp.zeros(max_batch, jnp.int32))
        self._upd: Dict[int, tuple] = {}
        self._upd_width = min(max_batch, 32)
        self._jit_mega = None
        self._jit_apply = None
        if self._spec is not None:
            # drafter state: per-slot history ring + written count —
            # device-resident like pos/act, mutated only by the spec
            # program and the activation scatters (_flush_updates)
            self._dev_hist = jnp.zeros(
                (max_batch, self._spec.history), jnp.int32)
            self._dev_hlen = jnp.zeros(max_batch, jnp.int32)
            self._jit_spec = None
        self._queue: collections.deque = collections.deque()
        self._finished: Dict[int, Request] = {}
        # deadline-carrying requests currently in the system: the per-step
        # expiry scan short-circuits to a single int check when zero (the
        # common serving case) — the r05 throughput dip was exactly this
        # class of always-on host work on the decode hot path
        self._n_deadlined = 0
        # resilience hooks cached at first step (module lookups + imports
        # off the per-step path; the lazy-import discipline is preserved —
        # nothing resilience-side loads until the engine actually steps)
        self._fault_hook = None
        self._device_loss_hook = None
        self._retry_stats_fn = None
        # host-side accounting: wall time of admission and of the decode
        # block as seen from the host. admit_host_s / decode_host_s /
        # prefill_host_s INCLUDE the waits on device values inside them; the
        # host's own work is step_wall_s less device_wait_s (the time inside
        # ``pt.serve.wait`` spans). steps / decode_blocks /
        # decode_block_steps (sum of block lengths) count dispatches;
        # programs_built counts every program variant's first call (the
        # n_steps variants in jax's own cache included, which
        # compile_cache_entries leaves out); step_max_s / step_max_wait_s
        # are the wall and the wait of the longest step so far that built
        # no program (a build is minutes of compiling, not a stall).
        # Plus the prefix-cache counters (docs/SERVING.md: hit_tokens /
        # miss_tokens feed serving_prefix_hit_rate; cow_copies / evictions
        # expose block lifecycle; compile_cache_entries is the
        # bounded-compile-cache telemetry, warned past ``compile_cache_cap``)
        n_kernel, n_kv = kernel_layers(self.caches["kv"])
        self.stats = {"admit_host_s": 0.0, "decode_host_s": 0.0,
                      "steps": 0, "step_wall_s": 0.0, "device_wait_s": 0.0,
                      "decode_blocks": 0, "decode_block_steps": 0,
                      "programs_built": 0, "step_max_s": 0.0,
                      "step_max_wait_s": 0.0,
                      # facts of the build, not rates: the layers that keep
                      # K and V, and those of them whose pools the paged
                      # kernel reads and the append writes in place
                      # (ops.paged_attention._kernel_takes)
                      "paged_kernel_layers": n_kernel, "kv_layers": n_kv,
                      # and those whose packed prefill chunk appends by the
                      # page (append_paged_chunk); 0 where no chunk is packed
                      "page_append_layers": (
                          page_append_layers(self.caches["kv"],
                                             self._chunk_tokens)
                          if prefix_cache is not None else 0),
                      # and those whose chunk form runs pt_paged_chunk
                      # (ops.paged_attention._chunk_kernel_takes): none off
                      # the TPU, for an int8 pool or where no chunk is packed
                      "chunk_kernel_layers": (
                          chunk_kernel_layers(self.caches["kv"],
                                              self._chunk_tokens)
                          if prefix_cache is not None else 0),
                      "compile_cache_entries": 0, "shed": 0,
                      "retry_attempts": 0, "retry_giveups": 0,
                      "fused_updates": 0,
                      # speculative decode counters (zero when spec off) —
                      # exported as pt_spec_proposed/accepted_total + the
                      # acceptance-rate gauge by the engine collector
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_steps": 0,
                      # mesh-sharded serving telemetry (zero on unsharded
                      # engines — the collector renders the families
                      # unconditionally so dashboards never lose them):
                      # accumulated per-device collective wire bytes of
                      # every sharded dispatch + sharded decode dispatches
                      "mesh_collective_bytes": 0.0, "mesh_decode_steps": 0,
                      # routed-expert counters out of the decode block,
                      # read back with its tokens (zero for a model whose
                      # paged_token_step returns no "moe_rows"): rows
                      # routed, experts with a row or more and the fullest
                      # expert's rows, each summed over expert layers and
                      # token steps, and the number of those (layer, step)
                      "moe_rows_routed": 0, "moe_experts_touched": 0,
                      "moe_layer_steps": 0, "moe_rows_max_expert": 0,
                      # picks the routers made (rows x experts a token, a
                      # layer and token step), where the model returns
                      # "moe_picks": of them moe_rows_routed went to experts
                      # held here (all, unless the layers hold a share)
                      "moe_picks": 0,
                      # state kept a sequence ("seq" layers): its bytes, the
                      # chunk rows that started one from zero, the runs of
                      # adjacent chunk rows of one sequence (a state is read
                      # and written back once a run), and the admissions
                      # that went without the radix trie for it
                      "seq_state_bytes": state_bytes(self.caches["kv"],
                                                     "seq"),
                      "seq_state_starts": 0,
                      "seq_state_runs": 0,
                      "prefix_declined_admissions": 0}
        if self._groups is not None:
            # the page groups (one a layer kind that keeps K and V): how
            # many, each group's pool and the pages live sequences map
            # (a gauge, set a step), its layers the kernel reads; window
            # pages mapped fresh and given back by a LIVING sequence;
            # window_pages_in_use_steps sums the window groups' pages in
            # use over the steps (over steps x their pools: the mean
            # share); hits honoured shorter than the trie matched
            self._layer_groups = getattr(
                model, "kv_layer_groups",
                lambda: [0] * len(self.caches["kv"]))()
            self.stats["kv_groups"] = len(self._groups.groups)
            self._group_gauges = [(f"kv_pages_in_use.{g.kind}", g)
                                  for g in self._groups.groups]
            for gi, g in enumerate(self._groups.groups):
                self.stats[f"kv_pool_pages.{g.kind}"] = g.num_blocks
                self.stats[f"kv_pages_in_use.{g.kind}"] = 0
                # a family of its own: the unlabeled ``paged_kernel_layers``
                # is the sum, and one family would count the layers twice
                mine = [e for e, at in zip(self.caches["kv"],
                                           self._layer_groups) if at == gi]
                self.stats[f"paged_kernel_layers_by_group.{g.kind}"] = \
                    kernel_layers(mine)[0]
                self.stats[f"chunk_kernel_layers_by_group.{g.kind}"] = \
                    chunk_kernel_layers(mine, self._chunk_tokens)
            self.stats.update(window_pages_released=0,
                              window_pages_allocated=0,
                              window_pages_in_use_steps=0,
                              prefix_hits_shortened=0)
        # per-program collective census (label -> per-dispatch wire bytes),
        # filled lazily as each sharded program first dispatches — feeds
        # the serving collector and mirrors the PT-COMM contract entries
        self._mesh_programs: Dict[str, float] = {}
        # (program, key) of every program variant called so far, with the
        # key as ``pt.serve.call`` writes it: a first call goes under
        # ``pt.serve.build`` (_call_built)
        self._built: Dict[tuple, str] = {}
        # what is in flight on the device, and the counters of the time it
        # is starved (_InFlight); the steps and the caller's intervals past
        # _LONG_S beside them: a step that built no program and took
        # longer, with its wall, its wait and its starved time, so that a
        # window's difference of stats says whether it held a stall and
        # whether the device or the host held it
        self._flight = _InFlight(self.stats)
        self.stats.update(steps_over_1s=0, steps_over_1s_wall_s=0.0,
                          steps_over_1s_wait_s=0.0,
                          steps_over_1s_starved_s=0.0)
        # terminal stamps of requests whose token values are still on the
        # device (the path without eos): _drain_pending writes them once
        # the values are on the host
        self._finish_marks: List["Request"] = []
        # int8 block-format occupancy gauge (pt_kv_quant_blocks): pool
        # pages held in quantized form — 0 on fp engines
        self._kv_quant_blocks = (pool_num_pages(self.caches["kv"])
                                 if self._kv_dtype == "int8" else 0)
        # int8 allocation hygiene (_reset_quant_blocks): one compiled
        # reset-scatter per power-of-two width
        self._jit_qreset: Dict[int, object] = {}
        if self.prefix_cache is not None:
            # prefix_hit_admissions: admissions that mapped cached pages;
            # state_snapshot_bytes: what the state rings kept with the
            # pages take
            self.stats.update(hit_tokens=0, miss_tokens=0, cow_copies=0,
                              evictions=0, prefill_host_s=0.0,
                              brownouts=0, brownout_steps=0, packed_rows=0,
                              prefix_hit_admissions=0,
                              state_snapshot_bytes=state_bytes(
                                  self.caches["kv"]))

        from ..jit.api import _collect_state

        _, tensors = _collect_state(model)
        self._params = [t._data for t in tensors]
        self._tensors = tensors
        self._jit_prefill: Dict[int, object] = {}
        # mesh placement (real meshes: one device_put pass; abstract
        # meshes: specs only — the audit path never touches devices).
        # Head-granularity check first: a column shard must hold WHOLE
        # heads (the kv pools shard along kv_heads; a mid-head split
        # would break the per-shard [.., heads, head_dim] reshape).
        self._param_specs = None
        if self._mesh is not None:
            cfg = getattr(model, "config", None)
            tp = int(self.mesh.tp)
            for f in ("num_attention_heads", "num_key_value_heads"):
                n = getattr(cfg, f, None)
                if n is not None and int(n) % tp:
                    raise ValueError(
                        f"{f}={n} not divisible by mesh tp={tp} — shards "
                        "must hold whole heads (KV pools shard kv_heads)")
            self._param_specs = [self._tp_param_spec(t) for t in tensors]
            if not self.mesh.abstract:
                self._place_on_mesh()

    def _req_tags(self, req: "Request") -> Dict:
        """Stamp tags for per-request trace sites (submit / shed / admit —
        the queue-wait stamp): the engine-level tags plus the request's
        workload tenant, so SLO attainment and the queue-wait histogram
        events split per tenant (observability/slo.py)."""
        if req.tenant is None:
            return self.trace_tags
        tags = dict(self.trace_tags)
        tags["tenant"] = req.tenant
        return tags

    def _span(self, name: str, **args):
        """A ``pt.<name>`` program span of this engine
        (observability/tracing.py ``program_span``); those of ``_PHASE_OF``
        tell the in-flight ledger which phase the host is in."""
        if name in _PHASE_OF:
            return _phase_span(self, name, **args)
        return program_span(name, self.tracer, self.trace_tags, **args)

    def _call_built(self, program: str, key, fn, *args, **kw):
        """Call a jitted program, under ``pt.serve.call`` (the in-flight
        ledger numbers it). The first call of each (program, key) — a
        trace and a compile or a cache load — runs under
        ``pt.serve.build`` and counts in ``stats["programs_built"]``."""
        k = (program, key)
        label = self._built.get(k)
        if label is not None:
            with _call_span(self, program, label):
                out = fn(*args, **kw)
        else:
            # no comma in a span's argument: the profiler splits on them
            label = "/".join(map(str, key)) if isinstance(key, tuple) \
                else str(key)
            with self._span("serve.build", program=program, key=str(key)):
                with _call_span(self, program, label):
                    out = first_call(fn, *args, **kw)
            self._built[k] = label
            self.stats["programs_built"] += 1
        self._flight.out = out
        return out

    # ---- public API ----
    def _refuse_over_groups(self, *whats):
        """``LayerStateError`` naming the kinds, for each ``(what, asked)``
        that a model with more than one page group was asked for."""
        decl = getattr(self.model, "kv_groups", list)()
        if len(decl) < 2:
            return
        for what, asked in whats:
            if asked:
                raise LayerStateError(
                    f"PT-SRV-009: {type(self.model).__name__} keeps layers "
                    f"of kinds {[k for k, _ in decl]} (a page group a kind, "
                    f"windows {[w for _, w in decl]}); they cannot be "
                    f"served by {what}")

    def add_request(self, req: Request) -> int:
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            raise EngineSaturated(
                f"engine queue at high-water mark ({self.max_queue} waiting, "
                f"{len(self._occupied)}/{self.max_batch} "
                "slots busy) — shed load or scale out")
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(req.prompt)} + max_new {req.max_new_tokens} "
                f"exceeds engine max_len {self.max_len}")
        if self.prompt_buckets and len(req.prompt) > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt {len(req.prompt)} exceeds largest prompt bucket "
                f"{self.prompt_buckets[-1]}")
        if self.prefix_cache is not None:
            short = self._groups.shortfall(
                self._pages_needed(len(req.prompt), req.max_new_tokens))
            if short:
                raise ValueError(
                    f"{short} — raise PrefixCacheConfig.extra_blocks or "
                    "shrink the request")
        # family-specific length limits (e.g. GPT's learned position table) —
        # the same validation generate() applies
        validate = getattr(self.model, "_validate_generate", None)
        if validate is not None:
            validate(len(req.prompt), len(req.prompt) + req.max_new_tokens)
        if self.tracer is not None:
            # stamp AFTER the caller-error validations (a ValueError'd
            # request never entered the system) but BEFORE the shed check
            # (a shed is a real terminal outcome of a real submission)
            self.tracer.submit(req.rid, len(req.prompt), req.max_new_tokens,
                               self._req_tags(req))
            try:
                self._shed_check(req)
            except RequestShed:
                self.tracer.shed(req.rid, self._req_tags(req))
                raise
        else:
            self._shed_check(req)
        req._engine = weakref.ref(self)
        req._enqueued_at = _time.monotonic()
        if req.deadline_s is not None:
            self._n_deadlined += 1
        # weighted admission order: lower priority value admits first; FIFO
        # within a class (insert behind every equal-or-higher-priority
        # waiter). The queue HEAD keeps its head-of-line semantics in
        # prefix mode — priorities only choose who the head is.
        q = self._queue
        i = len(q)
        while i > 0 and q[i - 1].priority > req.priority:
            i -= 1
        if i == len(q):
            q.append(req)
        else:
            q.insert(i, req)
        return req.rid

    def _shed_check(self, req: "Request"):
        """Deadline-feasibility admission control (PT-SRV-003): refuse at
        SUBMIT a request whose deadline cannot be met at the measured decode
        throughput — a typed :class:`RequestShed` now beats a deadline
        eviction after seconds of queue wait. Conservative by construction:
        no measured rate (cold engine) or no deadline means no shedding, and
        the backlog estimate counts only decode tokens ahead of the request
        (prefill compute is charged to the rate EMA, not the backlog)."""
        if (not self.shed_infeasible or req.deadline_s is None
                or self._ema_tok_s is None or self._ema_tok_s <= 0.0):
            return
        backlog = req.max_new_tokens
        for r in self._queue:
            if r.priority <= req.priority:
                backlog += r.max_new_tokens - r._n_out
        for r in self._occupied.values():   # O(active), never O(max_batch)
            backlog += max(0, r.max_new_tokens - r._n_out)
        est = backlog / self._ema_tok_s
        if est > req.deadline_s:
            self.stats["shed"] += 1
            raise RequestShed(
                f"PT-SRV-003: request rid={req.rid} shed at submit — "
                f"{backlog} backlog tokens at {self._ema_tok_s:.1f} tok/s "
                f"needs ~{est:.3f}s, past its {req.deadline_s:.3f}s deadline")

    def has_work(self) -> bool:
        return bool(self._queue) or bool(self._occupied)

    def active_slots(self) -> int:
        """Occupied slots (decoding + mid-prefill) — the O(1) counter the
        supervisor's ``load()`` and the metrics collectors read instead of
        scanning ``_slots`` (a 256-slot fleet pays that scan per request
        at routing time otherwise)."""
        return len(self._occupied)

    def step(self):
        """Advance active slots in ONE device program, then admit new
        requests while that program is in flight.

        Decode-first ordering (round 5, VERDICT "admission serializes with
        decode"): the decode scan for already-active slots is DISPATCHED
        before admission touches the host, so admission's prompt packing,
        prefill compile-cache lookups, and (on the eos path) its synchronous
        first-token materialization all overlap the in-flight decode block
        instead of stalling it. Newly admitted slots join the next block —
        on a single chip both programs execute serially anyway, so the
        schedule shift costs nothing while removing every host-side
        admission stall from the decode critical path. When all slots are
        idle, admission runs first so the wave starts without a wasted step.

        Without eos the whole schedule is DETERMINISTIC (a slot frees exactly
        when its request's max_new_tokens are scheduled), so no host decision
        ever needs a token VALUE: the engine runs to the next completion
        event per program, chains the last-token carry device-to-device, and
        defers all token materialization to ``_drain_pending`` — zero
        synchronous host round-trips in the decode path, exactly like
        ``generate()``'s async dispatch. eos-carrying batches pace at
        ``block_size`` and materialize each block (early exit needs the
        values). Host-side time is accounted in ``self.stats``
        (admit_host_s / decode_host_s, device waits included; step_wall_s
        and device_wait_s split the step into host work and waiting) and
        written as ``pt.serve.*`` program spans (docs/OBSERVABILITY.md)."""
        if self._fault_hook is None:
            from ..distributed.resilience.faults import (device_loss,
                                                         maybe_inject)

            self._fault_hook = maybe_inject
            self._device_loss_hook = device_loss
        t0 = _time.perf_counter()       # a stalled step's sleep is its own
        self._step_idx += 1
        # injection sites (docs/RESILIENCE.md): `serving.stall` sleeps the
        # step past its wall-clock budget (StepWatchdog / PT-SRV-002);
        # `serving.step` kills the engine mid-wave (ServingSupervisor
        # rebuild-from-journal / PT-SRV-001); `device.loss` removes devices
        # from the tp mesh (MeshDegraded / PT-SRV-008 — the elastic
        # reshard-and-resume drill). One global read each when no plan is
        # installed.
        self._fault_hook("serving.stall", f"step:{self._step_idx}")
        self._fault_hook("serving.step", f"step:{self._step_idx}")
        lost = self._device_loss_hook(f"step:{self._step_idx}")
        if lost > 0 and self._mesh is not None and not self.mesh.abstract:
            tp = int(self.mesh.tp)
            survivors = max(0, tp - lost)
            raise MeshDegraded(
                f"PT-SRV-008: tp={tp} device group lost {lost} device(s) "
                f"at step {self._step_idx} ({survivors} surviving) — "
                f"engine must reshard to a narrower mesh",
                lost=lost, survivors=survivors)
        stats, flight = self.stats, self._flight
        flight.step_begins(t0)
        wait0, built0 = stats["device_wait_s"], stats["programs_built"]
        starved0 = stats["device_starved_s"]
        maybe0 = stats["device_maybe_starved_s"]
        sched0 = self._sched_tokens
        windowed = self._groups is not None and self._groups.windowed
        released0 = self._groups.released if windowed else 0
        self._deferred_step = False
        try:
            with self._span("serve.step", step=self._step_idx,
                            occupied=len(self._occupied),
                            queued=len(self._queue)) as sp:
                try:
                    self._step_inner()
                finally:
                    if windowed:
                        sp.set(window_pages_released=self._book_window()
                               - released0)
                    flight.upto(_time.perf_counter())
                    sp.set(
                        starved_us=round(
                            1e6 * (stats["device_starved_s"] - starved0)),
                        maybe_starved_us=round(
                            1e6 * (stats["device_maybe_starved_s"] - maybe0)),
                        wait_us=round(
                            1e6 * (stats["device_wait_s"] - wait0)))
        finally:
            t1 = _time.perf_counter()
            dt = t1 - t0
            flight.step_ends(t1, self.has_work())
            stats["steps"] += 1
            stats["step_wall_s"] += dt
            if dt > _LONG_S and stats["programs_built"] == built0:
                stats["steps_over_1s"] += 1
                stats["steps_over_1s_wall_s"] += dt
                stats["steps_over_1s_wait_s"] += \
                    stats["device_wait_s"] - wait0
                stats["steps_over_1s_starved_s"] += \
                    stats["device_starved_s"] - starved0
            if dt > stats["step_max_s"] and \
                    stats["programs_built"] == built0:
                stats["step_max_s"] = dt
                stats["step_max_wait_s"] = stats["device_wait_s"] - wait0
            d = self._sched_tokens - sched0
            if d > 0 and dt > 0:
                rate = d / dt
                self._ema_tok_s = (rate if self._ema_tok_s is None
                                   else 0.7 * self._ema_tok_s + 0.3 * rate)
            if self._brownout_cfg is not None:
                self._brownout_tick()

    def _book_groups(self):
        """The page groups' gauges into ``stats``, once a step, where pages
        are mapped (inside ``pt.serve.admit``)."""
        stats = self.stats
        for key, group in self._group_gauges:
            stats[key] = group.in_use
        if self._groups.windowed:
            stats["window_pages_in_use_steps"] += sum(
                g.in_use for g in self._groups.windowed)

    def _book_window(self) -> int:
        """The window groups' running counts into ``stats``, at a step's
        end; returns the pages given back so far."""
        stats, groups = self.stats, self._groups
        stats["window_pages_released"] = groups.released
        stats["window_pages_allocated"] = groups.allocated
        stats["prefix_hits_shortened"] = groups.shortened
        return groups.released

    def _brownout_tick(self):
        """Hysteretic brownout state machine (docs/SERVING.md), evaluated
        once per step: sustained admission deferrals enter the degraded
        mode (idle cached blocks flushed, matching/registration and chunked
        prefill off); a sustained pressure-free streak with real pool
        headroom exits it."""
        cfg = self._brownout_cfg
        if self._brownout_active:
            self.stats["brownout_steps"] += 1
            free_frac = self._alloc.free_blocks / max(1, self._alloc.num_blocks)
            if not self._deferred_step and free_frac >= cfg.exit_free_frac:
                self._clear_steps += 1
                if self._clear_steps >= cfg.exit_after:
                    self._brownout_active = False
                    self._pressure_steps = self._clear_steps = 0
            else:
                self._clear_steps = 0
            return
        if self._deferred_step:
            self._pressure_steps += 1
            if self._pressure_steps >= cfg.enter_after:
                self._brownout_active = True
                self._clear_steps = 0
                self.stats["brownouts"] += 1
                # flush cached-idle blocks: under pressure the working set
                # outranks reuse — reclaimed pages go straight back to the
                # pool the deferred head is waiting on
                self._radix.evict_lru(self._alloc.num_blocks)
                self.stats["evictions"] = self._radix.evictions
        else:
            self._pressure_steps = 0

    def _step_inner(self):
        self._evict_expired()
        if self.prefix_cache is not None:
            # chunked-prefill budget: the decode batch is dispatched first,
            # then every mid-prefill slot advances by ONE chunk and newly
            # complete prompts take their first token — a long admit costs
            # each decode step one chunk of prefill, never a full prompt
            decoding = len(self._occupied) > len(self._prefill_next)
            if decoding:
                self._decode_block()
            t0 = _time.perf_counter()
            self._admit_span()
            self._prefill_tick()
            self.stats["admit_host_s"] += _time.perf_counter() - t0
            if not decoding:
                self._decode_block()
            return
        if not self._occupied:
            t0 = _time.perf_counter()
            self._admit_span()
            self.stats["admit_host_s"] += _time.perf_counter() - t0
            self._decode_block()
            return
        self._decode_block()
        t0 = _time.perf_counter()
        self._admit_span()
        self.stats["admit_host_s"] += _time.perf_counter() - t0

    def _admit_span(self):
        with self._span("serve.admit") as sp:
            q0 = len(self._queue)
            self._admit()
            if self._groups is not None:
                self._book_groups()
            sp.set(admitted=q0 - len(self._queue),
                   deferred=int(self._deferred_step))

    def _evict_expired(self):
        """Deadline enforcement: fail-and-free requests past ``deadline_s``
        (active slots AND still-queued requests) so a straggler can neither
        hog a slot forever nor hang its caller. Tokens already scheduled for
        an evicted slot stay in the pending readbacks — ``tokens`` remains
        complete up to the eviction point. A single int check when no
        deadline-carrying request is in the system."""
        if not self._n_deadlined:
            return
        with self._span("serve.admit", what="evict_expired"):
            self._evict_expired_scan()

    def _evict_expired_scan(self):
        now = _time.monotonic()

        def expired(r):
            return (r.deadline_s is not None and r._enqueued_at is not None
                    and now - r._enqueued_at > r.deadline_s)

        def fail(r):
            r.done = True
            r.failed = True
            r.error = (f"deadline exceeded: {now - r._enqueued_at:.3f}s > "
                       f"{r.deadline_s:.3f}s ({r._n_out} tokens scheduled)")
            self._mark_done(r)

        # O(active): walks the occupied dict, never all max_batch slots
        for i, req in sorted(self._occupied.items()):
            if expired(req):
                fail(req)
                # prefix mode: DECREFs (never frees) blocks other live
                # tables or the radix cache still reference
                self._release_slot(i)
        if any(expired(r) for r in self._queue):
            keep = collections.deque()
            for r in self._queue:
                if expired(r):
                    fail(r)
                else:
                    keep.append(r)
            self._queue = keep

    def _decode_block(self):
        t0 = _time.perf_counter()
        try:
            self._decode_block_inner()
        finally:
            self.stats["decode_host_s"] += _time.perf_counter() - t0

    def _decode_block_inner(self):
        with self._span("serve.decode.dispatch") as sp:
            # device-resident state: every admission/release queued since
            # the last block lands as ONE traced scatter program — the host
            # never rebuilds or uploads a [max_batch, pages] table. Rows of
            # free and still-prefilling slots stay on the parking page, so
            # the scan's dummy append can never touch a shared block
            # O(active): the decode set comes from the occupied dict (in
            # slot order), never a max_batch scan
            live = [(i, r) for i, r in sorted(self._occupied.items())
                    if not (self.prefix_cache is not None
                            and i in self._prefill_next)]
            if live and self._groups is not None \
                    and not self._groups.single:
                self._reserve_ahead(live)
            self._flush_updates()
            if not live:
                return
            # all-greedy block with verify-window headroom on every row
            # (the K+1 window writes k/v at positions pos-1 .. pos-1+K):
            # one speculative dispatch replaces the scan block. Sampling
            # rows keep the sampled mega-step; rows at the max_len
            # boundary finish on ordinary blocks.
            spec = (self._spec is not None
                    and not any(r.temperature > 0.0 for _, r in live)
                    and all(self.max_len - int(self._pos[i]) >= self._spec.k
                            for i, _ in live))
            if not spec:
                n, async_ok, do_sample = self._block_plan(live)
                out = self._dispatch_block(live, n, do_sample)
                seq = self._flight.called
                sp.set(n_steps=n, rows=len(live), do_sample=do_sample)
                self.stats["decode_blocks"] += 1
                self.stats["decode_block_steps"] += n
        if spec:
            return self._decode_spec_block(live)
        self._book_block(live, n, async_ok, out, seq)

    def _reserve_ahead(self, live):
        """Window groups: map pages for the positions the next decode block
        may write (a block never runs past ``advance``, ``_block_plan``),
        ``advance`` positions ahead at a time, so a row's table is rewritten
        once in ``advance / block`` blocks: the new row rides the slot's
        next update with the position, the flag and the sampling it has."""
        groups = self._groups
        horizon = (groups.advance if self._async_block(live)
                   else min(self.block_size, groups.advance))
        for i, req in live:
            pos = int(self._pos[i])
            end = min(self.max_len, pos + req.max_new_tokens - req._n_out)
            if groups.reserved(i) >= min(end, pos + horizon):
                continue
            got = groups.reserve(i, min(end, pos + groups.advance))
            if got is None:
                raise RuntimeError(
                    f"PT-SRV-012: no window page for rid={req.rid} (slot "
                    f"{i}) at position {pos}: a window group's pool holds "
                    f"what every slot may map at once, so pages are held "
                    f"outside the engine's account")
            if got:
                self._queue_update(i, groups.rows(i, self._slot_rows[i]),
                                   pos, True, req.seed, req.temperature,
                                   req.top_p, req.top_k)

    @staticmethod
    def _async_block(live) -> bool:
        return all(r.eos_token_id is None for _, r in live)

    def _block_plan(self, live):
        """(scan length, whether no row carries an eos id, whether any row
        samples) of the next decode block."""
        # block length: never decode past a request's max_new_tokens or the
        # engine max_len (pages beyond the table would clamp-corrupt)
        cap = min(min(r.max_new_tokens - r._n_out for _, r in live),
                  min(self.max_len - int(self._pos[i]) for i, _ in live))
        if self._groups is not None and not self._groups.single:
            # a window group maps pages ``advance`` positions ahead
            cap = min(cap, self._groups.advance)
        n = min(self.block_size, cap)
        async_ok = self._async_block(live)
        if async_ok:
            # run toward the next completion event; allowed scan lengths are
            # block_size * 2^k so the compiled-program set stays O(log) in
            # max_len (each distinct n compiles a full-model scan)
            stretch = self.block_size
            while stretch * 2 <= cap:
                stretch *= 2
            n = max(n, cap if cap <= self.block_size else stretch)
        n = max(1, n)
        return n, async_ok, bool(any(r.temperature > 0.0 for _, r in live))

    def _dispatch_block(self, live, n: int, do_sample: bool):
        """Dispatch ``pt_decode_block`` for ``n`` token steps; returns the
        device array of the block's tokens [slots, n]."""
        # ONE jitted mega-step over all rows: decode + sampling +
        # position advance in-graph, inactive rows masked by the
        # device-side act vector — admission never retraces
        if self._jit_mega is None:
            self._jit_mega = self._build_mega_jit()
            self._note_compiled()
        seeds_d, temps_d, tops_d, topks_d = self._dev_samp
        out, self._last_tok, new_kv, self._dev_pos = self._call_built(
            "pt_decode_block", (n, do_sample), self._jit_mega,
            self._params, self._last_tok, self.caches["kv"],
            self.caches["tables"], self._dev_pos, self._dev_act,
            seeds_d, temps_d, tops_d, topks_d, n_steps=n,
            do_sample=do_sample)
        self.caches = {"kv": new_kv, "tables": self.caches["tables"]}
        return out

    def _with_counters(self, out, ctr):
        """(Traced.) The block's tokens [slots, n] out of the scan's
        [n, slots]; where the model's token step returned ``counters``
        (name -> int array a step), each flattened to rows [k, n] under the
        tokens, so that they reach the host in the block's one transfer.
        The layout is noted for ``_book_counters``."""
        out = jnp.swapaxes(out, 0, 1)
        if not ctr:
            return out
        self._ctr_layout = [(name, tuple(ctr[name].shape[1:]))
                            for name in sorted(ctr)]
        rows = [jnp.swapaxes(ctr[name].reshape(out.shape[1], -1), 0, 1)
                for name, _ in self._ctr_layout]
        return jnp.concatenate([out] + [r.astype(out.dtype) for r in rows],
                               axis=0)

    def _book_counters(self, tail):
        """Add a block's counter rows (``tail`` [k, n], host values under
        the tokens of ``_with_counters``) into ``stats``. ``moe_rows``
        [expert layers, experts] a step: the rows each expert got, parked
        rows and rows past their EOS included (the device computed them)."""
        off = 0
        for name, shape in self._ctr_layout:
            size = int(np.prod(shape))
            a = tail[off:off + size].T.reshape((tail.shape[1],) + shape)
            off += size
            if name == "moe_picks":
                self.stats["moe_picks"] += int(a.sum())
            elif name == "moe_rows":
                st = self.stats
                st["moe_rows_routed"] += int(a.sum())
                st["moe_experts_touched"] += int((a > 0).sum())
                st["moe_layer_steps"] += int(a.shape[0] * a.shape[1])
                st["moe_rows_max_expert"] += int(a.max(-1).sum())

    def _book_block(self, live, n: int, async_ok: bool, out, seq: int):
        """Book a dispatched block's tokens (``out``, of call ``seq``): by
        the schedule alone where no row carries an eos id (values stay on
        the device until ``_drain_pending``), else from the values, read
        back here."""
        if async_ok:
            entries = []
            tok_marks = [] if self.tracer is not None else None
            with self._span("serve.emit") as sp:
                finished = 0
                for i, req in live:
                    took = min(n, req.max_new_tokens - req._n_out)
                    entries.append((i, req, took))
                    req._n_out += took
                    self._sched_tokens += took
                    if tok_marks is not None:
                        tok_marks.append((req.rid, req._n_out))
                    self._pos[i] += took
                    if req._n_out >= req.max_new_tokens:
                        req.done = True
                        finished += 1
                        self._mark_done(req)
                        self._release_slot(i)   # slot + pages are free again
                    else:
                        self._moved_on(i)
                sp.set(tokens=sum(e[2] for e in entries), finished=finished,
                       scheduled=True)
            # the rows' token progress is stamped when the values reach the
            # host (_drain_pending), never at dispatch
            self._pending.append((out, entries, tok_marks, False, seq))
            return
        # eos path: materialize (in generation order — drain older pendings
        # first so req.output stays ordered across an async->sync transition)
        self._drain_pending()
        with _wait_span(self, "decode_block", seq):
            out = np.asarray(out)
        tok_marks = [] if self.tracer is not None else None
        block_tokens = 0
        with self._span("serve.emit") as sp:
            if out.shape[0] > self.max_batch:
                self._book_counters(out[self.max_batch:])
            finished = 0
            for i, req in live:
                took = 0
                for j in range(n):
                    tok = int(out[i, j])
                    req.output.append(tok)
                    req._n_out += 1
                    took = j + 1
                    if ((req.eos_token_id is not None
                         and tok == req.eos_token_id)
                            or req._n_out >= req.max_new_tokens):
                        req.done = True
                        break
                self._pos[i] += took
                self._sched_tokens += took
                block_tokens += took
                if tok_marks is not None:
                    tok_marks.append((req.rid, req._n_out))
                if req.done:
                    finished += 1
                    self._mark_done(req)
                    self._release_slot(i)   # slot + its pages are free again
                else:
                    self._moved_on(i)
            sp.set(tokens=block_tokens, finished=finished)
        if tok_marks:
            self.tracer.tokens_batch(tok_marks, tags=self.trace_tags)

    def _moved_on(self, slot: int, pos: Optional[int] = None):
        """A living slot moved on: its next query sits at ``pos`` (a
        decoding slot's at ``_pos - 1``, where its next token is written),
        and the window-group pages that no query from there on reads go
        back to their pool (the device table keeps the stale entries; no
        reader looks behind the window)."""
        if self._groups is not None and not self._groups.single:
            self._groups.release_behind(
                slot, int(self._pos[slot]) - 1 if pos is None else pos)

    def run_until_done(self, max_steps: int = 100000):
        steps = 0
        while self.has_work() and steps < max_steps:
            self.step()
            steps += 1
        return self.finished()

    def finished(self) -> Dict[int, Request]:
        self._drain_pending()
        # control plane: land any queued release scatters so a drained
        # engine's device state (act mask / parked tables) is actually
        # drained, not pending the next decode dispatch
        self._flush_updates()
        # retry-registry snapshot rides here (control plane), NOT in step():
        # a per-step dict copy was measurable on the decode hot path
        if self._retry_stats_fn is None:
            from ..distributed.resilience.retry import retry_stats

            self._retry_stats_fn = retry_stats
        rs = self._retry_stats_fn()
        self.stats["retry_attempts"] = rs["attempts"]
        self.stats["retry_giveups"] = rs["giveups"]
        out, self._finished = self._finished, {}
        return out

    def _mark_done(self, req: "Request"):
        """Single chokepoint for request completion: surfaces the request
        in ``_finished``, retires its deadline from the expiry-scan
        counter, and stamps the terminal trace span (finish / evict /
        fail — the tracer infers the kind from failed+error)."""
        if req.deadline_s is not None:
            self._n_deadlined = max(0, self._n_deadlined - 1)
        self._finished[req.rid] = req
        if self.tracer is not None:
            if len(req.output) < req._n_out:
                # path without eos: the request is done by the schedule but
                # its last tokens are still on the device — the terminal
                # stamp (and the inter-token latency it closes) waits for
                # _drain_pending, behind the first-token stamp
                self._finish_marks.append(req)
            else:
                self._stamp_finish(req)

    def _stamp_finish(self, req: "Request"):
        self.tracer.finish(req.rid, req._n_out, failed=req.failed,
                           error=req.error, tags=self.trace_tags)

    def withdraw_queued(self, rid: int) -> bool:
        """Remove a still-WAITING request from the queue (never an admitted
        slot) — the fleet's drain-migration primitive. Returns False when
        the request is not in the queue."""
        for i, r in enumerate(self._queue):
            if r.rid == rid:
                del self._queue[i]
                if r.deadline_s is not None:
                    self._n_deadlined = max(0, self._n_deadlined - 1)
                return True
        return False

    # -- disaggregated-tier hooks (inference/disagg.py — docs/SERVING.md
    # "Disaggregated tiers") ------------------------------------------------
    def slot_of(self, rid: int) -> Optional[int]:
        """Slot currently serving ``rid`` (None when queued/finished) —
        O(active), never O(max_batch)."""
        for i, r in self._occupied.items():
            if r.rid == rid:
                return i
        return None

    def migration_ready(self) -> List[int]:
        """rids whose prefill is COMPLETE (first token scheduled, slot in
        the decode set) with decode work left — the prefill tier's
        migration candidates. Mid-chunk slots are not exportable: their
        cache holds a partial prompt and no sampling has happened."""
        out = []
        for i, r in sorted(self._occupied.items()):
            if self.prefix_cache is not None and i in self._prefill_next:
                continue
            if r._n_out >= 1 and not r.done:
                out.append(r.rid)
        return out

    def withdraw_active(self, rid: int) -> bool:
        """Release ``rid``'s ACTIVE slot without terminal bookkeeping —
        the KV-migration handoff (ownership moves to another engine; the
        request is neither done nor failed here). The caller must have
        exported the chain bytes FIRST: the decref'd pages may be
        re-mapped by the very next admission."""
        slot = self.slot_of(rid)
        if slot is None:
            return False
        req = self._slots[slot]
        if req.deadline_s is not None:
            self._n_deadlined = max(0, self._n_deadlined - 1)
        self._release_slot(slot)
        return True

    def admit_migrated(self, req: "Request", blocks: Sequence[int],
                       pos: int, last_tok: int) -> int:
        """Resume-at-position admission: occupy a free slot with a
        migrated finished-prefill chain whose pages the caller
        (:class:`~paddle_tpu.inference.disagg.KVChainCodec`) has already
        allocated (refcount 1) and filled with the exported bytes.

        Maps the table row, restores the device position and last-token
        carry, and registers the prompt's full pages in the radix cache so
        the migrated prefix is cache-visible to later admissions (first
        writer wins — a duplicate chain stays private). Decode then
        continues through the ordinary step programs: sample keys are
        stateless (``fold_in(seed, position)``), so given the same page
        bytes the continued stream is bit-identical to never migrating.
        Raises :class:`EngineSaturated` when no slot is free — the caller
        still owns ``blocks`` and must decref them."""
        if self.prefix_cache is None:
            raise ValueError("KV-chain splice needs a prefix-cache engine "
                             "(dynamic block tables over the refcounted "
                             "pool)")
        self._refuse_over_groups(
            ("a migrated chain (admit_migrated: PTKV1 carries one chain of "
             "pages a layer)", True))
        if self._seq_layers:
            raise LayerStateError(
                f"PT-SRV-009: layers {self._seq_layers} are of kind 'seq' "
                f"(SeqState, kept a slot): a migrated chain brings pages, "
                f"and the state of rid={req.rid} would be lost")
        if not self._free_slots:
            raise EngineSaturated(
                f"no free slot for migrated rid={req.rid} "
                f"({len(self._occupied)}/{self.max_batch} busy)")
        slot = self._free_slots.popleft()
        # int8 block hygiene: the chain's WRITTEN prefix was scattered
        # wholesale (bytes + scales) by the codec; the tail blocks the
        # chain will decode into are recycled allocations and need their
        # stale scales cleared
        if self._kv_dtype == "int8":
            n_written = max(0, -(-(int(pos) - 1) // self.page_size))
            self._reset_quant_blocks(list(blocks)[n_written:])
        row = np.full(self._maxp, self._park, np.int32)
        row[: len(blocks)] = blocks
        self._slot_rows[slot] = row
        self._slot_blocks[slot] = list(blocks)
        self._slots[slot] = req
        self._occupied[slot] = req
        req._engine = weakref.ref(self)
        # deadline clock RESTARTS at re-admission (recovery.py semantics:
        # a tier handoff is the operator's cost, not the request's)
        req._enqueued_at = _time.monotonic()
        if req.deadline_s is not None:
            self._n_deadlined += 1
        self._pos[slot] = int(pos)
        # control-plane eager scatter: the decode chain reads the carry
        # from device state, and migration happens once per request
        self._flight.call(_time.perf_counter())    # an eager program
        self._last_tok = self._flight.out = self._last_tok.at[slot].set(
            jnp.int32(int(last_tok)))
        # spec engines re-seed the drafter ring with prompt + delivered
        # tokens (minus the last-token carry restored above) so the
        # migrated stream drafts from its full history
        self._queue_update(slot, row, int(pos), True, req.seed,
                           req.temperature, req.top_p, req.top_k,
                           hist=(self._spec_seed(req.prompt,
                                                 extra=req.output[:-1])
                                 if self._spec is not None else None))
        n_full = len(req.prompt) // self.page_size
        if n_full and not self._brownout_active:
            self._radix.insert(req.prompt[: n_full * self.page_size],
                               list(blocks)[:n_full])
        return slot

    def _drain_pending(self):
        """Materialize deferred token blocks into request outputs.

        All host copies are STARTED asynchronously first: a synchronous
        readback stalls the host until that one transfer lands, so serial
        np.asarray calls would pay the transfers one after another (cost
        per readback not measured on the direct runtime)."""
        if not self._pending and not self._finish_marks:
            return
        for arr_dev, *_ in self._pending:
            try:
                arr_dev.copy_to_host_async()
            except AttributeError:
                pass
        tracer = self.tracer
        for arr_dev, entries, marks, first, seq in self._pending:
            with _wait_span(self, "pending", seq):
                arr = np.asarray(arr_dev)
            if arr.ndim == 2 and arr.shape[0] > self.max_batch:
                self._book_counters(arr[self.max_batch:])
            for row, req, took in entries:
                if arr.ndim == 1:           # prefill firsts [g]
                    req.output.append(int(arr[row]))
                else:                       # decode block [slots, n]
                    req.output.extend(int(t) for t in arr[row, :took])
            if marks and tracer is not None:
                # the stamps of values that were dispatched without a read:
                # TTFT and token progress mean "on the host"
                if first:
                    tracer.first_tokens(marks, tags=self.trace_tags)
                else:
                    tracer.tokens_batch(marks, tags=self.trace_tags)
        self._pending.clear()
        if self._finish_marks:
            marks, self._finish_marks = self._finish_marks, []
            if tracer is not None:
                for req in marks:
                    self._stamp_finish(req)

    # ---- internals ----
    def _release_slot(self, i: int):
        """Free slot ``i``. Prefix mode DECREFS the slot's blocks (a shared
        prefix block stays alive while any other table or the radix cache
        references it — freeing it would corrupt the survivors) and parks
        the slot's decode-table row via the next traced scatter — freed
        pages may be re-mapped by the very next admission, and the
        inactive row's dummy append must never touch them. The device
        table is authoritative: there is no host mirror to drift."""
        if self._slots[i] is not None:
            self._occupied.pop(i, None)
            self._free_slots.append(i)
        self._slots[i] = None
        self._pos[i] = 0
        if self.prefix_cache is not None:
            blocks = self._slot_blocks[i]
            if blocks:
                self._alloc.decref(blocks)
            self._slot_blocks[i] = None
            self._slot_rows[i] = None
            self._prefill_next.pop(i, None)
            self._groups.release(i)
        self._queue_update(i, None, 0, False)

    # -- mega-step machinery (module docstring / docs/SERVING.md) ----------
    def _queue_update(self, slot: int, row, pos: int, act: bool,
                      seed: int = 0, temp: float = 0.0, top_p: float = 1.0,
                      top_k: int = 0, hist=None):
        """Queue one slot's device-state change (activation or release).
        The LATEST update per slot wins — a release followed by a re-admit
        of the same slot in one step collapses to the admit — and
        everything queued lands as ONE traced scatter program at the next
        decode dispatch. ``row=None`` means the parking row (release) or
        an unchanged static table (engines without a prefix cache).
        ``hist`` (spec engines) is the slot's drafter seed ``(ring_row,
        hlen)`` — None resets the ring (release / non-spec engines ignore
        it)."""
        self._upd[slot] = (row, int(pos), bool(act), int(seed), float(temp),
                           float(top_p), int(top_k), hist)

    def _flush_updates(self):
        """Apply queued slot updates to the device-resident step state in
        bounded-width batches of ONE scatter program each. Padding entries
        carry index ``max_batch`` — jax drops out-of-bounds scatter
        updates, so a single compiled program serves every update count."""
        if not self._upd:
            return
        items = list(self._upd.items())
        self._upd.clear()
        with_spec = self._spec is not None
        if self._jit_apply is None:
            with_tables = self.prefix_cache is not None

            def pt_slot_update(tables, pos, act, seeds, temps, tops, topks,
                               hist, hlen, idx, urows, upos, uact, useeds,
                               utemps, utops, utopks, uhist, uhlen):
                if with_tables:
                    # one table a page group (a lone array with one group)
                    tables = jax.tree_util.tree_map(
                        lambda t, u: t.at[idx].set(u), tables, urows)
                if with_spec:
                    hist = hist.at[idx].set(uhist)
                    hlen = hlen.at[idx].set(uhlen)
                return (tables, pos.at[idx].set(upos),
                        act.at[idx].set(uact), seeds.at[idx].set(useeds),
                        temps.at[idx].set(utemps), tops.at[idx].set(utops),
                        topks.at[idx].set(utopks), hist, hlen)

            self._jit_apply = jax.jit(pt_slot_update)
            self._note_compiled()
        W = self._upd_width
        with_tables = self.prefix_cache is not None
        H = self._spec.history if with_spec else 1
        for lo in range(0, len(items), W):
            batch = items[lo:lo + W]
            idx = np.full(W, self.max_batch, np.int32)
            # engines without a prefix cache have static slot-owned tables:
            # the apply program ignores urows, so don't build/upload the
            # [W, maxp] buffer at all (a 1-element dummy keeps the
            # signature); same for the drafter ring on non-spec engines
            urows = (self._groups.parked(W)
                     if with_tables else np.zeros((1, 1), np.int32))
            uhist = (np.zeros((W, H), np.int32) if with_spec
                     else np.zeros((1, 1), np.int32))
            uhlen = np.zeros(W if with_spec else 1, np.int32)
            upos = np.zeros(W, np.int32)
            uact = np.zeros(W, bool)
            useeds = np.zeros(W, np.int32)
            utemps = np.zeros(W, np.float32)
            utops = np.ones(W, np.float32)
            utopks = np.zeros(W, np.int32)
            for j, (slot, (row, pos, act, seed, temp, top_p, top_k,
                           hist_seed)) in enumerate(batch):
                idx[j] = slot
                if with_tables and row is not None:
                    self._groups.put(urows, j, row)
                if with_spec and hist_seed is not None:
                    uhist[j], uhlen[j] = hist_seed
                upos[j] = pos
                uact[j] = act
                useeds[j] = seed
                utemps[j] = temp
                utops[j] = top_p
                utopks[j] = top_k
            seeds_d, temps_d, tops_d, topks_d = self._dev_samp
            # without a drafter: host dummies (an eager jnp.zeros would be a
            # device program of its own a flush, unseen by the ledger)
            hist_d = self._dev_hist if with_spec else uhist
            hlen_d = self._dev_hlen if with_spec else uhlen
            tables, self._dev_pos, self._dev_act, s, t, p, k, hist_d, \
                hlen_d = self._call_built(
                    "pt_slot_update", (), self._jit_apply,
                    self.caches["tables"], self._dev_pos, self._dev_act,
                    seeds_d, temps_d, tops_d, topks_d, hist_d, hlen_d, idx,
                    urows, upos, uact, useeds, utemps, utops, utopks,
                    uhist, uhlen)
            self._dev_samp = (s, t, p, k)
            if with_spec:
                self._dev_hist, self._dev_hlen = hist_d, hlen_d
            self.caches = {"kv": self.caches["kv"], "tables": tables}
            self.stats["fused_updates"] += len(batch)

    # -- mesh-sharded serving (docs/SERVING.md "Sharded serving") ----------
    def _tp_param_spec(self, t):
        """Column-parallel placement rule for ONE parameter: a 2-dim
        weight whose LAST logical axis is an output-feature axis (heads /
        mlp / vocab) shards that axis across tp; everything else —
        o_proj/down_proj (output axis "embed"), the embedding, norms —
        replicates. Splitting only output dims is what keeps every output
        element's contraction whole on one device (the identity
        contract); the matching all_gathers live in the model layers
        (distributed.auto_parallel.serving_sharding)."""
        from jax.sharding import PartitionSpec as P

        axes = getattr(t, "logical_axes", None) or ()
        data = t._data
        if data.ndim == 2 and axes and axes[-1] in ("heads", "mlp",
                                                    "vocab"):
            tp = int(self.mesh.tp)
            if data.shape[-1] % tp:
                raise ValueError(
                    f"param {axes} shape {tuple(data.shape)}: output dim "
                    f"{data.shape[-1]} not divisible by mesh tp={tp}")
            return P(None, self._mesh_axis)
        return P()

    def _kv_spec(self):
        """ONE PartitionSpec prefix covering EVERY kv-pool leaf: pools
        are [pages, kv_heads, page, head_dim] (the int8 format adds
        [pages, kv_heads] absmax scales; a lane-dense pool of narrow
        heads is [pages, kv_heads // f, page, 128], folded so that each
        shard holds whole groups: ``kv_pool_shape(shards=tp)``) — all
        shard axis 1, the kv_heads axis, matching the column-sharded
        k/v projections.
        Appends, decode gathers, COW page copies, quant resets and the
        int8 scatter-max scales are then shard-local forever: no decode
        step ever reshards the pool, and per-(page, head) quantization
        partitions EXACTLY across head shards."""
        from jax.sharding import PartitionSpec as P

        return P(None, self._mesh_axis)

    def _arg_specs(self, kinds):
        from jax.sharding import PartitionSpec as P

        out = []
        for k in kinds:
            if k == "params":
                out.append(self._param_specs)
            elif k == "kv":
                out.append(self._kv_spec())
            else:
                out.append(P())
        return tuple(out)

    def _place_on_mesh(self):
        """One-time initial reshard: params column-sharded, kv pools
        sharded along kv_heads, block tables + device-resident step
        state replicated. After this no hot-path dispatch moves resident
        bytes between placements — the per-step collectives are exactly
        the activation all_gathers the census records. Stamped as one
        "reshard" tracer span (the only reshard boundary the engine
        has)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        t0 = None if self.tracer is None else self.tracer.now()
        mesh = self._mesh
        rep = NamedSharding(mesh, P())
        kv_sh = NamedSharding(mesh, self._kv_spec())
        put = jax.device_put
        self._params = [put(p, NamedSharding(mesh, s))
                        for p, s in zip(self._params, self._param_specs)]
        kv = jax.tree_util.tree_map(lambda x: put(x, kv_sh),
                                    self.caches["kv"])
        self.caches = {"kv": kv, "tables": put(self.caches["tables"], rep)}
        self._last_tok = put(self._last_tok, rep)
        self._dev_pos = put(self._dev_pos, rep)
        self._dev_act = put(self._dev_act, rep)
        self._dev_samp = tuple(put(x, rep) for x in self._dev_samp)
        if self._spec is not None:
            self._dev_hist = put(self._dev_hist, rep)
            self._dev_hlen = put(self._dev_hlen, rep)
        if self.tracer is not None:
            self.tracer.span("reshard", None, t0, tags=self.trace_tags,
                             tp=int(self.mesh.tp))

    def _mesh_census(self, name, key, fn, args):
        """Per-dispatch collective wire bytes of a freshly built sharded
        program: ONE extra trace (``make_jaxpr`` — no XLA compile, and
        BEFORE the first real call, so donation has not consumed any
        input buffer), censused by the PT-COMM walker. Recorded per
        program for the serving collector."""
        from ..static.comm.collectives import iter_collectives

        label = name if not key else name + "@" + ",".join(map(str, key))
        total = sum((c.total_wire_bytes
                     for c in iter_collectives(jax.make_jaxpr(fn)(*args))),
                    0.0)
        self._mesh_programs[label] = total
        if self.tracer is not None:
            self.tracer.instant("mesh_census", None, self.trace_tags,
                                program=label, wire_bytes=total)
        return total

    def _mesh_jit(self, run, in_kinds, out_kinds, donate, static_names=(),
                  name="program", count_stat=None):
        """jit(shard_map(run)) under the engine's placement contract:
        ``in_kinds``/``out_kinds`` name each argument/output "params"
        (per-param column specs), "kv" (kv_heads-sharded pool tree) or
        anything else (replicated); ``out_kinds`` may be the bare string
        "kv" for programs returning the pool tree alone. The body is
        traced inside :func:`serving_shard_axis`, the trace-time channel
        telling model layers to all_gather their column-sharded outputs.

        Returns a dispatcher callable. Statics (the mega-step's
        ``n_steps``/``do_sample``) select a cached
        ``jit(shard_map(partial(run, **statics)))`` — shard_map has no
        static-argument support, and baking them per variant keeps the
        ``donated_invars`` visible on the traced pjit equation exactly
        where PT-COST-003 audits them. First dispatch per variant runs
        the collective census once; every dispatch then accumulates the
        per-dispatch wire bytes into ``stats['mesh_collective_bytes']``."""
        from functools import partial

        from ..distributed.auto_parallel.serving_sharding import \
            serving_shard_axis

        axis = self._mesh_axis
        in_specs = self._arg_specs(in_kinds)
        out_specs = (self._kv_spec() if out_kinds == "kv"
                     else self._arg_specs(out_kinds))

        def build(**statics):
            fn = partial(run, **statics) if statics else run

            def body(*args):
                with serving_shard_axis(axis):
                    return fn(*args)

            # "XLA Modules" reads jit_<name>: the program's own pt_ name
            body.__name__ = body.__qualname__ = run.__name__
            sm = jax.shard_map(body, mesh=self._mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            return jax.jit(sm, donate_argnums=donate)

        cache = {}

        def dispatch(*args, **statics):
            key = tuple(statics[n] for n in static_names)
            ent = cache.get(key)
            if ent is None:
                fn = build(**statics)
                ent = cache[key] = (fn,
                                    self._mesh_census(name, key, fn, args))
            fn, per_dispatch = ent
            self.stats["mesh_collective_bytes"] += per_dispatch
            if count_stat is not None:
                self.stats[count_stat] += 1
            return fn(*args)

        return dispatch

    def _build_mega_jit(self):
        """The jitted mega-step EXACTLY as ``step`` dispatches it —
        donation included. tools/audit_program_cost.py traces this (pure
        tracing, no compile) so the audited ``donated_invars`` are the
        production program's, not a parallel declaration. Mesh engines
        get the same program as jit(shard_map(...)) behind a
        static-variant dispatcher (``_mesh_jit``) — byte-identical
        output, per-shard compute."""
        donate = self._MEGA_DONATE_ARGNUMS
        if self._mesh is not None:
            return self._mesh_jit(
                self._mega_step_fn(), self._MEGA_ARG_NAMES,
                ("rep", "rep", "kv", "rep"), donate,
                static_names=("n_steps", "do_sample"), name="mega_step",
                count_stat="mesh_decode_steps")
        return jax.jit(self._mega_step_fn(),
                       static_argnames=("n_steps", "do_sample"),
                       donate_argnums=donate)

    def _mega_step_fn(self):
        """The mega-step program (tools/lint_graph.py records and lints
        this — the one program an engine dispatches per decode block):
        decode ``n_steps`` tokens for every row at per-row positions,
        sample in-graph, and advance the device-side positions,
        with inactive rows masked by the ``act`` vector (they step a
        parked dummy row whose output the host ignores) — so admissions
        and completions never change the program shape and never retrace.
        The per-row math is ``generate()``'s: ``paged_token_step`` then
        ``sample_rows`` under the key (request seed, position)."""
        from ..core import autograd_engine
        from ..jit.api import _Swap

        def pt_decode_block(params, toks, kv, tables, pos, act, seeds, temps,
                            tops, topks, n_steps, do_sample):
            caches = {"kv": kv, "tables": tables}
            pos_vec = jnp.where(act, pos, 1) - 1

            def body(carry, _):
                tok, cs, p = carry
                if self._seq_layers:
                    # row i is slot i; a row that does not decode leaves
                    # its slot's state as it is
                    cs = dict(cs, seq_live=act)
                with autograd_engine.no_grad(), _Swap(self._tensors, params):
                    logits, cs = self.model.paged_token_step(tok, cs, p)
                ctr = cs.pop("counters", None)
                if do_sample:
                    keys = _fold_keys(seeds, p + 1)
                    nxt = sample_rows(logits, keys, temps, tops, topks)
                else:
                    nxt = _greedy(logits)
                return (nxt, cs, p + 1), (nxt, ctr)

            (tok, cs, _), (out, ctr) = jax.lax.scan(
                body, (toks, caches, pos_vec), None, length=n_steps)
            new_pos = jnp.where(act, pos + n_steps, pos)
            return self._with_counters(out, ctr), tok, cs["kv"], new_pos

        return pt_decode_block

    # -- speculative multi-token decoding (docs/SERVING.md) ----------------
    def _build_spec_jit(self):
        """The jitted speculative verify mega-step EXACTLY as dispatched —
        donation included (kv / pos / drafter ring+length are the carries;
        tools/audit_program_cost.py traces this, PT-COST-003 audits the
        ``donated_invars``)."""
        donate = self._SPEC_DONATE_ARGNUMS
        if self._mesh is not None:
            return self._mesh_jit(
                self._spec_step_fn(), self._SPEC_ARG_NAMES,
                ("rep", "rep", "rep", "kv", "rep", "rep", "rep"), donate,
                name="spec_verify", count_stat="mesh_decode_steps")
        return jax.jit(self._spec_step_fn(), donate_argnums=donate)

    def _spec_step_fn(self):
        """ONE speculative dispatch over all rows (draft -> verify ->
        accept/rollback, all in-graph):

        1. DRAFT: the device-resident prompt-lookup drafter
           (:func:`ngram_draft`) proposes K tokens per row from its
           history ring — no draft model, no host sync.
        2. VERIFY: the K+1 window [last_token, drafts] runs through the
           model's ``paged_verify_step`` (append-then-gather +
           absolute-position masking — ``ops.paged_verify_attention``),
           scoring every position in one pass.
        3. ACCEPT: greedy exact-match accept/reject
           (:func:`spec_accept`) keeps the longest draft prefix whose
           tokens equal the verify argmaxes, plus ONE bonus token — the
           emitted stream is byte-identical to the non-speculative
           mega-step. Rejected appends need no scatter rollback: the
           per-row position only advances over accepted tokens, so
           rejected k/v sits beyond the attended window and is
           overwritten as decode proceeds (the engine's standard
           pad-append invariant). Inactive rows are masked (emit 0) by
           the same act-vector idiom as the mega-step — churn never
           retraces."""
        from ..core import autograd_engine
        from ..jit.api import _Swap

        spec = self._spec
        K, N, H = spec.k, spec.ngram, spec.history
        accept_all = spec._unsafe_accept_all

        def pt_spec_block(params, toks, kv, tables, pos, act, hist, hlen,
                          caps):
            pos_vec = jnp.where(act, pos, 1) - 1
            drafts = ngram_draft(hist, hlen, toks, K, N)
            window = jnp.concatenate([toks[:, None], drafts], axis=1)
            caches = {"kv": kv, "tables": tables}
            with autograd_engine.no_grad(), _Swap(self._tensors, params):
                logits, caches = self.model.paged_verify_step(
                    window, caches, pos_vec)
            targets = jnp.argmax(logits, -1).astype(jnp.int32)
            if accept_all:
                # DRILL-ONLY control arm (spec_decode_divergence): trust
                # every draft — the verification this path skips is what
                # keeps greedy streams byte-identical
                targets = jnp.concatenate([drafts, targets[:, K:]], axis=1)
            out, emit, _ = spec_accept(drafts, targets,
                                       jnp.where(act, caps, 0))
            emit = jnp.where(act, emit, 0)
            last = jnp.take_along_axis(
                out, jnp.clip(emit - 1, 0, K)[:, None], axis=1)[:, 0]
            last = jnp.where(emit > 0, last, toks)
            # ring append: the OLD last token plus all emitted-but-newest
            # tokens enter the ring; the newest rides the last-token carry
            vals = jnp.concatenate([toks[:, None], out[:, :K]], axis=1)
            j = jnp.arange(K + 1)[None, :]
            widx = jnp.where(j < emit[:, None],
                             (hlen[:, None] + j) % H, H)   # H: dropped
            hist = hist.at[jnp.arange(hist.shape[0])[:, None],
                           widx].set(vals)
            hlen = hlen + emit
            new_pos = jnp.where(act, pos + emit, pos)
            return out, emit, last, caches["kv"], new_pos, hist, hlen

        return pt_spec_block

    def _decode_spec_block(self, live):
        """Dispatch one speculative verify step and book its variable
        per-row emission. Unlike the deterministic-schedule scan path,
        acceptance is data-dependent — the per-row emit counts (a [B]
        int32 vector) are read back synchronously per dispatch; the token
        matrix itself stays a deferred readback (``_drain_pending``)
        unless an eos-carrying row needs the values."""
        spec = self._spec
        K = spec.k
        with self._span("serve.decode.dispatch", n_steps=K + 1,
                        rows=len(live), do_sample=False):
            caps = np.zeros(self.max_batch, np.int32)
            for i, r in live:
                caps[i] = min(r.max_new_tokens - r._n_out,
                              self.max_len - int(self._pos[i]))
            if self._jit_spec is None:
                self._jit_spec = self._build_spec_jit()
                self._note_compiled()
            (out_dev, emit_dev, self._last_tok, new_kv, self._dev_pos,
             self._dev_hist, self._dev_hlen) = self._call_built(
                "pt_spec_block", (), self._jit_spec,
                self._params, self._last_tok, self.caches["kv"],
                self.caches["tables"], self._dev_pos, self._dev_act,
                self._dev_hist, self._dev_hlen, jnp.asarray(caps))
            self.caches = {"kv": new_kv, "tables": self.caches["tables"]}
            seq = self._flight.called
        with _wait_span(self, "spec_emit", seq):
            emit = np.asarray(emit_dev)     # the one sync read ([B] int32)
        # proposal counter derives from the already-synced emit vector —
        # never a second device readback per dispatch (each one stalls
        # the host on the device); the ACCEPTED counter is
        # credited per row below from the post-eos/cap delivered count, so
        # acceptance telemetry tracks delivered-token truth
        self.stats["spec_proposed"] += K * len(live)
        self.stats["spec_steps"] += 1
        any_eos = any(r.eos_token_id is not None for _, r in live)
        out = None
        if any_eos:
            # materialize in generation order (drain older pendings first)
            self._drain_pending()
            with _wait_span(self, "spec_block", seq):
                out = np.asarray(out_dev)
        entries = []
        tok_marks = [] if self.tracer is not None else None
        with self._span("serve.emit") as sp:
            total = self._book_spec(live, emit, out, entries, tok_marks)
            sp.set(tokens=total, finished=sum(1 for _, r in live if r.done))
        if tok_marks and out is not None:
            # at K>1 a dispatch emits a variable token count (``tokens`` of
            # the pt.serve.emit span above); the rows' progress is booked
            # here only when the values are on the host
            self.tracer.tokens_batch(tok_marks, tags=self.trace_tags)
        if entries:
            self._pending.append((out_dev, entries, tok_marks, False, seq))

    def _book_spec(self, live, emit, out, entries, tok_marks) -> int:
        """Book one speculative dispatch's per-row emission; returns the
        tokens delivered."""
        total = 0
        for i, req in live:
            took = int(emit[i])
            if out is not None:
                used = 0
                for jj in range(took):
                    tok = int(out[i, jj])
                    req.output.append(tok)
                    req._n_out += 1
                    used = jj + 1
                    if (req.eos_token_id is not None
                            and tok == req.eos_token_id):
                        req.done = True
                        break
                took = used
            else:
                entries.append((i, req, took))
                req._n_out += took
            # accepted drafts among DELIVERED tokens (eos/cap truncation
            # included): every delivered token past the first of a
            # dispatch is an accepted draft
            self.stats["spec_accepted"] += max(0, took - 1)
            self._pos[i] += took
            self._sched_tokens += took
            total += took
            if tok_marks is not None:
                tok_marks.append((req.rid, req._n_out))
            if req._n_out >= req.max_new_tokens:
                req.done = True
            if req.done:
                self._mark_done(req)
                self._release_slot(i)
        return total

    def _reset_quant_blocks(self, blocks):
        """int8 allocation hygiene: zero the page bytes AND the per-block
        absmax scales of freshly-allocated blocks. A recycled page keeps
        its previous occupant's scale, and quantize-on-append grows scales
        monotonically (scatter-max) — without this reset a new request's
        first tokens would quantize under the STALE (possibly much larger)
        scale, so a warm re-admission through recycled pages would emit
        different bytes than its cold run: the warm==cold guarantee would
        silently narrow to never-recycled pools. Eager control-plane
        dispatch (once per admission, never on the decode hot path),
        padded to power-of-two widths with an out-of-range index jax
        drops — compiled programs stay O(log pool)."""
        if self._kv_dtype != "int8" or not len(blocks):
            return
        from ..ops.paged_attention import QuantizedKVPool

        W = 1
        while W < len(blocks):
            W *= 2
        fn = self._jit_qreset.get(W)
        if fn is None:
            def pt_kv_reset(kv, idx):
                out = []
                for k, v in kv:
                    out.append((
                        QuantizedKVPool(k.data.at[idx].set(0),
                                        k.scale.at[idx].set(0.0)),
                        QuantizedKVPool(v.data.at[idx].set(0),
                                        v.scale.at[idx].set(0.0))))
                return out

            fn = self._jit_qreset[W] = jax.jit(pt_kv_reset)
            self._note_compiled()
        npages = pool_num_pages(self.caches["kv"])
        idx = np.full(W, npages, np.int32)     # pad: out of range, dropped
        idx[:len(blocks)] = blocks
        self.caches = {"kv": self._call_built("pt_kv_reset", W, fn,
                                              self.caches["kv"],
                                              jnp.asarray(idx)),
                       "tables": self.caches["tables"]}

    def _spec_seed(self, prompt, extra=()):
        """Drafter seed for a slot activation: the last ``history`` tokens
        of prompt (+ already-delivered tokens on migration), laid out in
        ring order — token with global index g at slot g % H — so the spec
        program's ring arithmetic continues seamlessly. The CURRENT last
        token stays out (it rides the device last-token carry and enters
        the ring on the next spec step)."""
        H = self._spec.history
        toks = np.asarray(prompt, np.int32).reshape(-1)
        if len(extra):
            toks = np.concatenate(
                [toks, np.asarray(extra, np.int32).reshape(-1)])
        hlen = len(toks)
        row = np.zeros(H, np.int32)
        tail = toks[max(0, hlen - H):]
        if len(tail):
            row[np.arange(hlen - len(tail), hlen) % H] = tail
        return row, hlen

    def _cow_copy_batch(self, pairs):
        """All of an admission wave's COW copies in ONE device dispatch.
        Padded to a power-of-two
        width with park->park self-copies so the compiled-program set
        stays O(log max_batch); the sources stay pinned (incref'd by
        ``_try_admit_prefix``) until the copy is dispatched — ``evict_lru``
        under a later admission in the same wave must not reclaim them
        first."""
        W = 1
        while W < len(pairs):
            W *= 2
        groups = self._groups
        fn = self._jit_cow_batch.get(W)
        if fn is None:
            def pt_cow_copy(kv, src, dst):
                return groups.copy_pages(kv, self._layer_groups, src, dst)

            fn = self._jit_cow_batch[W] = jax.jit(pt_cow_copy)
            self._note_compiled()
        # a pair is (src, dst) of the full group, then of each window group
        src = [np.full(W, g.park, np.int32) for g in groups.groups]
        dst = [x.copy() for x in src]
        for j, pair in enumerate(pairs):
            for gi, (s, d) in enumerate(pair):
                src[gi][j] = s
                dst[gi][j] = d
        as_args = lambda xs: jax.tree_util.tree_map(jnp.asarray,
                                                    groups.parts(xs))
        self.caches = {"kv": self._call_built("pt_cow_copy", W, fn,
                                              self.caches["kv"],
                                              as_args(src), as_args(dst)),
                       "tables": self.caches["tables"]}
        for gi, g in enumerate(groups.groups):
            g.alloc.decref([pair[gi][0] for pair in pairs])

    def _pages_needed(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new) // self.page_size)

    def _note_compiled(self):
        """Bounded-compile-cache telemetry (PT 1's PT-TRACE-001 churn lint,
        in-process): serving programs key on shapes — admission group size,
        prompt bucket, chunk width, sampling mode — so a shape-churning
        workload compiles without bound. Track the entry count and warn
        past ``compile_cache_cap``. (The mega-step counts as one entry; its
        n_steps variants live in jax's own jit cache.)"""
        n = (len(self._jit_prefill) + len(self._jit_qreset)
             + (self._jit_mega is not None) + (self._jit_apply is not None))
        if self._spec is not None:
            n += self._jit_spec is not None
        if self.prefix_cache is not None:
            n += (len(self._jit_chunk) + len(self._jit_first)
                  + len(self._jit_cow_batch))
        self.stats["compile_cache_entries"] = n
        if n > self.compile_cache_cap:
            import warnings

            warnings.warn(
                f"PT-TRACE-001: serving engine holds {n} compiled programs "
                f"(cap {self.compile_cache_cap}) — admission-shape churn is "
                "recompiling per wave; pin prompt_buckets / prefill_chunk "
                "or raise compile_cache_cap", RuntimeWarning, stacklevel=3)

    def _admit(self):
        if self.prefix_cache is not None:
            return self._admit_prefix()
        return self._admit_legacy()

    # -- prefix-cache admission + chunked prefill ---------------------------
    def _admit_prefix(self):
        """Admission with radix prefix matching over the refcounted pool.

        FIFO with head-of-line blocking on pool exhaustion: when the queue
        head cannot get its blocks (even after LRU eviction of idle cached
        blocks) it stays queued and later arrivals wait behind it — the
        queue then fills and ``add_request`` backpressures via
        ``EngineSaturated``; the allocator never overcommits shared blocks
        (tools/fault_drill.py drills exactly this)."""
        from ..distributed.resilience.faults import resource_hold

        if not self._queue:
            return
        cow_wave = []
        while self._free_slots and self._queue:
            req = self._queue[0]
            held = resource_hold("serving.block_pool", f"rid:{req.rid}")
            if held:
                self._alloc.hold(held)
            if not self._try_admit_prefix(self._free_slots[0], req, cow_wave):
                # deferral = the pool could not serve the head even after
                # LRU eviction — the brownout pressure signal
                self._deferred_step = True
                break
            self._queue.popleft()
            self._free_slots.popleft()
        if cow_wave:
            self._cow_copy_batch(cow_wave)
        self.stats["evictions"] = self._radix.evictions

    def _try_admit_prefix(self, slot: int, req: "Request",
                          cow_wave: list) -> bool:
        page = self.page_size
        prompt = req.prompt
        n_full = len(prompt) // page
        # brownout: admission stops consulting the radix cache entirely —
        # every block is freshly allocated (still through the refcounted
        # pool), which is exactly the cache-off working-set shape
        matched = (self._radix.match(prompt[: n_full * page])
                   if n_full and not self._brownout_active
                   and not self._seq_layers else [])
        if matched:
            # what every page group still covers of it where the request
            # will read (PageGroups.honour: all of it with one group)
            matched = self._groups.honour(matched)
            if not matched:
                self.stats["prefix_declined_admissions"] += 1
        if self._seq_layers:
            # no page carries the state a hit would resume from: the trie is
            # neither asked nor fed (_emit_first), and the prompt prefills
            # from position 0, which starts the slot's state from zero
            self.stats["prefix_declined_admissions"] += 1
        cow_src = None
        if matched and len(matched) * page == len(prompt):
            # FULL-prompt hit: nothing to prefill, but the first-token
            # re-step rewrites position L-1 inside the last shared block —
            # copy-on-write it into a private page first
            cow_src = matched[-1]
            matched = matched[:-1]
        need = self._pages_needed(len(prompt), req.max_new_tokens)
        fresh_n = need - len(matched)          # includes the COW copy
        # Pin the matched chain (and the COW source) BEFORE the
        # eviction-capable alloc: they are refcount-0 CACHED-IDLE until
        # incref'd, so evict_lru under shortfall could reclaim them and
        # alloc would hand the same pages back as `fresh` — double-mapping
        # a block in this slot's table (decode appends into the suffix
        # copy would clobber the shared prefix k/v).
        pinned = matched + ([cow_src] if cow_src is not None else [])
        self._alloc.incref(pinned)
        fresh = self._alloc.alloc(fresh_n, evict=self._radix.evict_lru)
        if fresh is None:
            self._alloc.decref(pinned)
            return False                       # pool exhausted — defer
        # the window groups' pages: the hit's, and fresh ones as far as the
        # first packed call writes (the rest as the sequence goes)
        cached = (len(matched) + (cow_src is not None)) * page
        window_cow = self._groups.admit(
            slot, matched, cow_src,
            min(need * page, cached + self._groups.advance))
        if window_cow is None:
            self._alloc.decref(pinned + fresh)
            return False                       # a window group is short
        # int8 block hygiene BEFORE any write (incl. the COW copy below,
        # which overwrites its dst wholesale anyway): recycled pages must
        # not leak their previous occupant's absmax scale into this
        # request's quantization
        self._reset_quant_blocks(fresh)
        cached = len(matched) * page
        if cow_src is not None:
            dst = fresh[0]
            # the whole admission wave's COW copies batch into one program
            # (_cow_copy_batch); the source stays pinned until that
            # dispatch so eviction cannot reclaim it first
            cow_wave.append([(cow_src, dst)] + [c[0] for c in window_cow])
            self.stats["cow_copies"] += 1
            blocks = matched + [dst] + fresh[1:]
            cached = len(prompt)
        else:
            blocks = matched + fresh
        row = np.full(self._maxp, self._park, np.int32)
        row[: len(blocks)] = blocks
        self._slot_rows[slot] = row
        self._slot_blocks[slot] = blocks
        self._slots[slot] = req
        self._occupied[slot] = req
        # next uncached write position; == len(prompt) means straight to
        # the first-token re-step. The slot joins the decode batch (and the
        # device-side table) only once prefill completes.
        self._prefill_next[slot] = cached
        self.stats["hit_tokens"] += cached
        self.stats["miss_tokens"] += len(prompt) - cached
        if cached:
            # the mapped pages bring their state rings: the request resumes
            # every state layer at the end of the hit (on a full-prompt
            # hit, from the COW copy's ring, whose slot of position L-1 the
            # re-step rewrites and whose L-2, L-3 it reads)
            self.stats["prefix_hit_admissions"] += 1
        if self.tracer is not None:
            now = _time.monotonic()
            self.tracer.admit(
                req.rid, now - (req._enqueued_at or now),
                hit_tokens=cached, miss_tokens=len(prompt) - cached,
                tags=self._req_tags(req))
        return True

    def _prefill_tick(self):
        """One packed prefill call over the mid-prefill slots, then the
        first-token re-step (+ radix registration) for slots whose prompts
        are fully written. Chunks are batched across slots at per-row
        offsets; the re-step runs through ``paged_token_step`` so warm
        (cache-hit) and cold admissions share one program per shape — the
        warm==cold bit-identity guarantee (see
        ops.paged_prefill_attention)."""
        if not self._prefill_next:
            return
        t0 = _time.perf_counter()
        try:
            with self._span("serve.prefill") as sp:
                left0 = self._prefill_left()
                rows0 = self.stats["packed_rows"]
                self._prefill_tick_inner()
                sp.set(tokens=left0 - self._prefill_left(),
                       rows=self.stats["packed_rows"] - rows0)
        finally:
            self.stats["prefill_host_s"] += _time.perf_counter() - t0

    def _prefill_left(self) -> int:
        """Prompt tokens of mid-prefill slots not yet written."""
        return sum(len(self._slots[s].prompt) - nxt
                   for s, nxt in self._prefill_next.items())

    def _prefill_tick_inner(self):
        chunkers = [(s, self._slots[s]) for s in sorted(self._prefill_next)
                    if self._prefill_next[s] < len(self._slots[s].prompt)]
        if chunkers:
            # prompt-packing prefill (_run_pack): several short prompts
            # — and several chunks of one long prompt — advance in ONE
            # call per step
            self._run_pack(chunkers)
            while self._brownout_active and any(
                    self._prefill_next[s] < len(r.prompt)
                    for s, r in chunkers):
                # brownout disables chunked INTERLEAVING: the whole prompt
                # prefills this tick, trading decode overlap for zero
                # extra mid-prefill state under pressure. Same compiled
                # chunk programs, run to completion.
                self._run_pack([(s, r) for s, r in chunkers
                                if self._prefill_next[s] < len(r.prompt)])
        ready = [(s, self._slots[s]) for s in sorted(self._prefill_next)
                 if self._prefill_next[s] >= len(self._slots[s].prompt)]
        if ready:
            self._first_token(ready)

    def _prefill_row(self, s: int, req: "Request"):
        """Table row handed to the prefill-chunk program: the slot's REAL
        prompt pages, with everything beyond them (the decode-headroom
        blocks) parked. A chunk's pad tail (ids right-padded to the chunk
        width) scatters k/v at positions past the prompt — with the full
        row those bytes land in the slot's future decode blocks. Harmless
        under fp (masked, then overwritten) but corrosive under int8: the
        pad garbage feeds the blocks' scatter-max absmax scales, which are
        MONOTONE — a cold admission's decode blocks would quantize under
        pad-inflated scales while a warm full-prompt hit (no prefill, no
        pads) would not, silently breaking warm==cold byte-identity.
        Parking the pad extent keeps decode blocks byte-virgin on every
        admission path. Pads inside the final partially-filled prompt page
        still land there (same bytes on every path: pad k/v depends only
        on the pad token id and its absolute position)."""
        return self._groups.prompt_rows(
            self._rows_of(s), -(-len(req.prompt) // self.page_size))

    def _rows_of(self, slot: int):
        """A slot's table rows as the programs take them: the full group's
        [maxp] row, or with window groups a tuple of one row a group."""
        return self._groups.rows(slot, self._slot_rows[slot])

    def _chunk_fn(self, g: int):
        """The compiled prefill-chunk program for ``g`` rows
        (params, ids, kv, rows, starts), as ``_run_pack`` dispatches it."""
        fn = self._jit_chunk.get(g)
        if fn is None:
            from ..core import autograd_engine
            from ..jit.api import _Swap

            def pt_prefill_chunk(params, ids, kv, rows, starts, *extra):
                # extra, by what the layers keep: ("state") each row's
                # count of real tokens, so that a padded tail leaves their
                # state alone; ("seq") each row's slot and its count of
                # positions whose state is kept
                sub = {"kv": kv, "tables": rows}
                extra = list(extra)
                if self._state_layers:
                    sub["valid"] = extra.pop(0)
                if self._seq_layers:
                    sub["seq"] = (extra.pop(0), extra.pop(0))
                with autograd_engine.no_grad(), _Swap(self._tensors, params):
                    sub = self.model.paged_prefill_chunk(ids, sub, starts)
                return sub["kv"]

            donate = self._CHUNK_DONATE_ARGNUMS
            if self._mesh is not None:
                fn = self._mesh_jit(pt_prefill_chunk, self._CHUNK_ARG_NAMES,
                                    "kv", donate, name=f"prefill_chunk@{g}")
            else:
                fn = jax.jit(pt_prefill_chunk, donate_argnums=donate)
            self._jit_chunk[g] = fn
            self._note_compiled()
        return fn

    def _run_pack(self, group):
        """Prompt-packing prefill: flatten (slot, chunk)
        pairs into the rows of ONE ``paged_prefill_chunk`` call — several
        short prompts complete their whole prefill, and a long prompt
        advances several chunks, in a single device program instead of
        one chunk per slot per step.

        Safe by the same absolute-position-masking argument as chunked
        prefill (``ops.paged_prefill_attention``): every row's k/v is
        appended before any row's attention reads, and a query attends
        exactly the keys at positions <= its own — so a later chunk of
        the same prompt reads the earlier chunk's pages written IN THE
        SAME program, bit-identical to running the chunks sequentially.
        Rows are assigned breadth-first (one chunk per slot per pass), so
        every mid-prefill slot advances at least one chunk per step — the
        interleaving guarantee — and ``PrefixCacheConfig.pack_rows``
        bounds the extra rows. A model whose layers keep a state a
        sequence (kind ``"seq"``) gets the rows so picked BY SEQUENCE,
        ordered by (slot, offset): its scan carries one sequence's state
        from a row to the next (``ops.ssd.ssd_scan_pooled``), and K and V
        do not care, by the argument above. Row counts are bucketed to
        powers of two with parked dummy rows, last in either order, so
        admission-width churn at 128+ slots compiles O(log max_batch)
        variants, not one per width."""
        C = self._chunk_tokens
        budget = max(len(group), self._pack_rows)
        offs = {s: self._prefill_next[s] for s, _ in group}
        # window groups: a row's window pages are mapped before it is
        # taken, never more than ``advance`` ahead (a slot takes slot_rows
        # rows a call), and a slot a group has no page for this step waits
        windowed = not self._groups.single
        takes = {s: self._groups.slot_rows if windowed else budget
                 for s, _ in group}
        rows = []
        progress = True
        while len(rows) < budget and progress:
            progress = False
            for s, req in group:
                if len(rows) >= budget:
                    break
                if offs[s] < len(req.prompt) and takes[s] > 0:
                    end = min(offs[s] + C, len(req.prompt))
                    if windowed and self._groups.reserve(s, end) is None:
                        takes[s] = 0
                        self._deferred_step = True
                        continue
                    takes[s] -= 1
                    rows.append((s, req, offs[s]))
                    offs[s] = end
                    progress = True
        if not rows:
            return
        if self._seq_layers:
            rows.sort(key=lambda row: (row[0], row[2]))
            self.stats["seq_state_runs"] += self._seq_runs(rows)
        g = 1
        while g < len(rows):
            g *= 2
        t0_tr = None if self.tracer is None else self.tracer.now()
        ids = np.zeros((g, C), np.int32)
        starts = np.zeros(g, np.int32)
        real = np.zeros(g, np.int32)       # parked dummy rows: no real token
        # "seq" layers: each row's slot (dummy rows: none) and how many of
        # its positions leave their state behind: all but the prompt's last
        # token, which the first-token program steps at its true position
        seq_slot = np.full(g, self.max_batch, np.int32)
        seq_keep = np.zeros(g, np.int32)
        trows = self._groups.parked(g)
        for r, (s, req, off) in enumerate(rows):
            if off % self.page_size:
                raise PageAlignmentError(
                    f"PT-SRV-010: packed prefill of rid={req.rid} (slot {s}) "
                    f"at offset {off}, no multiple of the page "
                    f"({self.page_size}): the chunk writes whole pages")
            chunk = req.prompt[off: off + C]
            ids[r, : len(chunk)] = chunk
            starts[r] = off
            real[r] = len(chunk)
            seq_slot[r] = s
            seq_keep[r] = min(len(chunk), len(req.prompt) - 1 - off)
            self._groups.put(trows, r, self._prefill_row(s, req))
        new_kv = self._call_built(
            "pt_prefill_chunk", g, self._chunk_fn(g), self._params,
            jnp.asarray(ids), self.caches["kv"],
            jax.tree_util.tree_map(jnp.asarray, trows),
            jnp.asarray(starts),
            *([jnp.asarray(real)] if self._state_layers else []),
            *([jnp.asarray(seq_slot), jnp.asarray(seq_keep)]
              if self._seq_layers else []))
        self.caches = {"kv": new_kv, "tables": self.caches["tables"]}
        self.stats["packed_rows"] += len(rows)
        if self._seq_layers:
            self.stats["seq_state_starts"] += sum(
                1 for _, _, off in rows if off == 0)
        for s, req in group:
            nxt = self._prefill_next[s]
            if offs[s] > nxt:
                self._prefill_next[s] = offs[s]
                if windowed:
                    self._prompt_written(s, req, offs[s])
                if self.tracer is not None:
                    self.tracer.prefill_chunk(req.rid, t0_tr, offs[s] - nxt,
                                              tags=self.trace_tags)

    def _prompt_written(self, slot: int, req: "Request", upto: int):
        """Window groups, after a packed call wrote the prompt as far as
        ``upto``: the whole pages before the prompt's last token go into
        the trie (the last token's page waits for the first-token program,
        which rewrites it: ``_emit_first``), and only then does the slot
        let go of the window pages the rest of the prompt will not read."""
        page = self.page_size
        n = min(upto, len(req.prompt) - 1) // page
        if n and not self._brownout_active and not self._seq_layers:
            self._groups.written(slot, req.prompt[: n * page],
                                 self._slot_blocks[slot][:n])
        self._moved_on(slot, min(upto, len(req.prompt) - 1))

    def _seq_runs(self, rows) -> int:
        """The runs of adjacent rows of one slot among a pack's rows
        ``(slot, request, offset)`` for ``"seq"`` layers; PackOrderError
        (PT-SRV-011) unless every slot has ONE run whose chunks follow one
        another."""
        runs, seen, prev = 0, set(), (None, None)
        for s, req, off in rows:
            if s != prev[0]:
                runs += 1
                ok = s not in seen
                seen.add(s)
            else:
                ok = off == prev[1] + self._chunk_tokens
            if not ok:
                raise PackOrderError(
                    f"PT-SRV-011: packed prefill of rid={req.rid} (slot {s}) "
                    f"at offset {off} follows slot {prev[0]} at offset "
                    f"{prev[1]}: the rows of a sequence whose layers keep "
                    f"its state must be adjacent and rising")
            prev = (s, off)
        return runs

    def _first_token(self, ready):
        """Re-step the last REAL prompt token at its true position (k/v
        rewrite into a private/COW block, logits over exactly the real
        prompt) and sample the first token — the chunked-path analogue of
        the bucketed re-step; then register the prompt's full blocks in the
        radix cache and promote the slot into the decode batch (activation
        rides the next traced scatter, and group widths are bucketed to
        powers of two — dummy rows re-step the parking page at position 0
        and scatter to slot index ``max_batch``, which jax drops — so
        admission-wave width churn never retraces)."""
        g = 1
        while g < len(ready):
            g *= 2
        do_sample = any(r.temperature > 0.0 for _, r in ready)
        last = np.zeros(g, np.int32)
        rows = self._groups.parked(g)
        ints = np.zeros((g, 4), np.int32)
        ints[:, 0] = 1                       # dummy rows re-step position 0
        ints[:, 3] = self.max_batch          # dummy scatter index: dropped
        floats = np.zeros((g, 2), np.float32)
        floats[:, 1] = 1.0
        for r, (s, req) in enumerate(ready):
            last[r] = req.prompt[-1]
            self._groups.put(rows, r, self._rows_of(s))
            ints[r] = (len(req.prompt), req.seed, req.top_k, s)
            floats[r] = (req.temperature, req.top_p)
        fn = self._jit_first.get((g, do_sample))
        if fn is None:
            from ..core import autograd_engine
            from ..jit.api import _Swap

            def pt_first_token(params, last, kv, rows, last_tok, ints,
                               floats, _sample=do_sample):
                true_len, seed, top_k, slots_ = (ints[:, 0], ints[:, 1],
                                                 ints[:, 2], ints[:, 3])
                temp, top_p = floats[:, 0], floats[:, 1]
                sub = {"kv": kv, "tables": rows}
                if self._seq_layers:
                    # a dummy row's slot is out of range: no state changes
                    sub["seq_slots"] = slots_
                with autograd_engine.no_grad(), _Swap(self._tensors, params):
                    logits, sub = self.model.paged_token_step(
                        last, sub, true_len - 1)
                if _sample:
                    keys = _fold_keys(seed, true_len)
                    nxt = sample_rows(logits, keys, temp, top_p, top_k)
                else:
                    nxt = _greedy(logits)
                return nxt, sub["kv"], last_tok.at[slots_].set(nxt)

            donate = self._FIRST_DONATE_ARGNUMS
            if self._mesh is not None:
                fn = self._mesh_jit(pt_first_token, self._FIRST_ARG_NAMES,
                                    ("rep", "kv", "rep"), donate,
                                    name=f"first_token@{g}")
            else:
                fn = jax.jit(pt_first_token, donate_argnums=donate)
            self._jit_first[(g, do_sample)] = fn
            self._note_compiled()
        firsts_dev, new_kv, self._last_tok = self._call_built(
            "pt_first_token", (g, do_sample), fn,
            self._params, jnp.asarray(last), self.caches["kv"],
            jax.tree_util.tree_map(jnp.asarray, rows), self._last_tok,
            jnp.asarray(ints), jnp.asarray(floats))
        self.caches = {"kv": new_kv, "tables": self.caches["tables"]}
        seq = self._flight.called
        any_eos = any(r.eos_token_id is not None for _, r in ready)
        firsts = None
        if any_eos:
            with _wait_span(self, "first_token", seq):
                firsts = np.asarray(firsts_dev)
        with self._span("serve.emit", tokens=len(ready)) as sp:
            self._emit_first(ready, firsts, firsts_dev, seq)
            sp.set(finished=sum(1 for _, r in ready if r.done))

    def _emit_first(self, ready, firsts, firsts_dev, seq: int):
        """Book an admission wave's first tokens, of call ``seq`` (values
        in ``firsts`` when an eos id made the engine read them, else still
        on the device), register the prompts' blocks and promote the slots
        to decoding."""
        entries = []
        ft_marks = [] if self.tracer is not None else None
        for row, (slot, req) in enumerate(ready):
            n_full = len(req.prompt) // self.page_size
            if n_full and not self._brownout_active \
                    and not self._seq_layers:
                # register AFTER the full prompt (incl. the re-step rewrite)
                # is scheduled — later admissions are device-ordered behind
                # these writes; first writer wins on duplicate chains.
                # Brownout skips registration: blocks must return to the
                # pool the moment the request finishes, not linger cached.
                self._groups.written(
                    slot, req.prompt[: n_full * self.page_size],
                    self._slot_blocks[slot][:n_full])
            del self._prefill_next[slot]
            req._n_out += 1
            self._sched_tokens += 1
            if ft_marks is not None:
                ft_marks.append((req.rid, req._n_out))
            self._pos[slot] = len(req.prompt) + 1
            self._moved_on(slot)
            # activation rides the next traced scatter: table row,
            # position, active flag, sampling params — and on spec engines
            # the drafter ring seeded with the prompt — in one update (the
            # device table is authoritative)
            self._queue_update(slot, self._rows_of(slot),
                               len(req.prompt) + 1, True, req.seed,
                               req.temperature, req.top_p, req.top_k,
                               hist=(self._spec_seed(req.prompt)
                                     if self._spec is not None else None))
            if firsts is not None:
                req.output.append(int(firsts[row]))
            else:
                entries.append((row, req, 1))
        if ft_marks and firsts is not None:
            # one lock acquisition for the whole admission wave's
            # first-token + token stamps (not one per slot). Values still
            # on the device are stamped when they land (_drain_pending)
            self.tracer.first_tokens(ft_marks, tags=self.trace_tags)
        for row, (slot, req) in enumerate(ready):
            if ((firsts is not None and req.eos_token_id is not None
                 and int(firsts[row]) == req.eos_token_id)
                    or req._n_out >= req.max_new_tokens):
                req.done = True
                self._mark_done(req)
                self._release_slot(slot)
        if entries:
            self._pending.append((firsts_dev, entries, ft_marks, True, seq))

    def _admit_legacy(self):
        """Admit queued requests into free slots — ONE batched prefill call
        per prompt bucket (per-request prefills pay one dispatch and one
        first-token readback each; batching amortizes both and runs the
        prompt chunks as one device program)."""
        if not self._queue:
            return
        take = []
        while self._free_slots and self._queue:
            take.append((self._free_slots.popleft(), self._queue.popleft()))
        if not take:
            return
        if self._kv_dtype == "int8":
            # legacy layout: slot i statically owns pages [i*maxp,
            # (i+1)*maxp) — reset the admitted slots' pages so recycled
            # scales never shape the new prompts' quantization
            self._reset_quant_blocks([s * self._maxp + j
                                      for s, _ in take
                                      for j in range(self._maxp)])
        # group by (bucket, padded?): exact-length rows must take the
        # no-restep program — their first token then comes from the SAME
        # prefill-chunk logits generate(cache_impl='paged') computes, keeping
        # the token-exact equality guarantee even at bf16 softmax near-ties
        groups: Dict[tuple, list] = {}
        for slot, req in take:
            b = self._bucket(len(req.prompt))
            groups.setdefault((b, len(req.prompt) != b), []).append(
                (slot, req))
        for (padded, _), grp in groups.items():
            # the prefill program also scatters the group's first tokens into
            # the device-resident last-token carry (no eager device ops here:
            # each is its own dispatch; cost not measured on the direct
            # runtime)
            with self._span("serve.prefill", tokens=padded * len(grp),
                            rows=len(grp)):
                firsts_dev = self._prefill_group(padded, grp)
                seq = self._flight.called
            firsts = None
            if any(r.eos_token_id is not None for _, r in grp):
                with _wait_span(self, "first_token", seq):
                    firsts = np.asarray(firsts_dev)
            with self._span("serve.emit", tokens=len(grp)) as sp:
                self._emit_group(grp, firsts, firsts_dev, seq)
                sp.set(finished=sum(1 for _, r in grp if r.done))

    def _emit_group(self, grp, firsts, firsts_dev, seq: int):
        """Book a legacy admission group's first tokens, of call ``seq``,
        and occupy its slots (``firsts``: the values when an eos id made
        the engine read them, else None — they stay on the device)."""
        entries = []
        ft_marks = [] if self.tracer is not None else None
        for row, (slot, req) in enumerate(grp):
            self._slots[slot] = req
            self._occupied[slot] = req
            req._n_out += 1
            self._sched_tokens += 1
            if self.tracer is not None:
                now = _time.monotonic()
                self.tracer.admit(req.rid,
                                  now - (req._enqueued_at or now),
                                  miss_tokens=len(req.prompt),
                                  tags=self._req_tags(req))
                ft_marks.append((req.rid, req._n_out))
            self._pos[slot] = len(req.prompt) + 1
            # static slot-owned tables in this layout: activation only
            # flips act/pos/sampling (+ the spec drafter seed) via the
            # traced scatter
            self._queue_update(slot, None, len(req.prompt) + 1, True,
                               req.seed, req.temperature, req.top_p,
                               req.top_k,
                               hist=(self._spec_seed(req.prompt)
                                     if self._spec is not None else None))
            if firsts is not None:
                req.output.append(int(firsts[row]))
            else:
                entries.append((row, req, 1))
        if ft_marks and firsts is not None:
            # one lock acquisition for the group's first-token stamps;
            # values still on the device are stamped as they land
            self.tracer.first_tokens(ft_marks, tags=self.trace_tags)
        for row, (slot, req) in enumerate(grp):
            if ((firsts is not None and req.eos_token_id is not None
                 and int(firsts[row]) == req.eos_token_id)
                    or req._n_out >= req.max_new_tokens):
                req.done = True
                self._mark_done(req)
                self._release_slot(slot)
        if entries:
            self._pending.append((firsts_dev, entries, ft_marks, True, seq))

    def _bucket(self, n: int) -> int:
        if not self.prompt_buckets:
            return n
        for b in self.prompt_buckets:
            if b >= n:
                return b
        return n  # unreachable: add_request validates against the last bucket

    def _prefill_group(self, padded: int, grp):
        """Prefill a GROUP of slots sharing one padded prompt length; returns
        the first sampled token per slot.

        Compiles once per (PADDED length, restep, sampling, group size) — with
        ``prompt_buckets`` that is once per bucket per admission width; the
        re-step of the last real token keeps bucketed numerics exact (see
        module docstring). ``_admit`` groups exact-length rows separately so
        they take the no-restep program (same prefill-chunk logits as
        ``generate(cache_impl='paged')``, token-exact even at bf16 ties)."""
        slots = [s for s, _ in grp]
        reqs = [r for _, r in grp]
        restep = any(len(r.prompt) != padded for r in reqs)
        ids = np.stack([
            np.concatenate([r.prompt,
                            np.zeros(padded - len(r.prompt), np.int32)])
            for r in reqs])
        do_sample = any(r.temperature > 0.0 for r in reqs)
        fn = self._jit_prefill.get((padded, restep, do_sample))
        if fn is None:
            from ..core import autograd_engine
            from ..jit.api import _Swap

            def pt_prefill_group(params, ids, kv, all_tables, last_tok, ints,
                                 floats, _restep=restep, _sample=do_sample):
                # ints [g, 4]: true_len, seed, top_k, slot; floats [g, 2]:
                # temperature, top_p — packed so an admission moves THREE
                # host->device buffers total (ids/ints/floats); the table
                # gather and last-token scatter run inside this program
                true_len, seed, top_k, slots_ = (ints[:, 0], ints[:, 1],
                                                 ints[:, 2], ints[:, 3])
                temp, top_p = floats[:, 0], floats[:, 1]
                sub = {"kv": kv, "tables": all_tables[slots_]}
                with autograd_engine.no_grad(), _Swap(self._tensors, params):
                    logits, sub = self.model._decode_chunk(
                        ids, sub, 0, None, None)
                    if _restep:
                        # re-step the last REAL token at its true position:
                        # identical k/v rewrite, logits over the real prompt
                        # only (pad columns beyond true_len not yet attended)
                        last = jnp.take_along_axis(
                            ids, true_len[:, None] - 1, axis=1)[:, 0]
                        logits, sub = self.model.paged_token_step(
                            last, sub, true_len - 1)
                if _sample:
                    # sample_rows takes temp<=0 rows to argmax — mixed
                    # greedy/sampling groups stay exact for the greedy rows
                    keys = _fold_keys(seed, true_len)
                    nxt = sample_rows(logits, keys, temp, top_p, top_k)
                else:
                    nxt = _greedy(logits)
                return nxt, sub["kv"], last_tok.at[slots_].set(nxt)

            fn = self._jit_prefill[(padded, restep, do_sample)] = jax.jit(
                pt_prefill_group)
            self._note_compiled()
        ints = np.asarray([[len(r.prompt), r.seed, r.top_k, s]
                           for s, r in grp], np.int32)
        floats = np.asarray([[r.temperature, r.top_p] for _, r in grp],
                            np.float32)
        firsts, new_kv, self._last_tok = self._call_built(
            "pt_prefill_group", (padded, restep, do_sample, len(grp)), fn,
            self._params, jnp.asarray(ids), self.caches["kv"],
            self.caches["tables"], self._last_tok,
            jnp.asarray(ints), jnp.asarray(floats))
        self.caches = {"kv": new_kv, "tables": self.caches["tables"]}
        return firsts                      # device array — materialized lazily
