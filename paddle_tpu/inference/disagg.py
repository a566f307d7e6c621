"""Disaggregated prefill/decode serving tiers with KV-block migration.

Production serving at heavy traffic splits prefill (compute-bound, bursty)
from decode (latency-bound, steady) onto separate replicas — SURVEY.md's
inference layer (AnalysisPredictor pools + the fleet_executor message bus
for distributed inference) is the reference shape, ROADMAP open item 3 the
charter. Every primitive already existed: chunked prefill advances slots
one chunk per step, pages are refcounted with COW ``copy_pages``
(ops/paged_attention.py), the journal re-admits work on another replica
byte-identically (fleet failover is exactly a KV-less migration), and the
router already does radix-affinity placement. This module adds the missing
piece — moving a finished prefill's KV pages between replica pools:

- :class:`KVChainCodec` — serialize a slot's finished-prefill state (page
  chain in block-table order, absolute position, prompt token ids,
  delivered tokens, sampling key state) into a self-describing artifact
  with per-page crc32 and a chain digest, and splice it into a destination
  engine's ``BlockAllocator`` pool: fresh pages at refcount 1, the table
  row mapped, the device position/last-token carry restored, and the
  prompt chain radix-inserted so migrated prefixes become cache-visible.
  Pool/slot shortfall raises ``EngineSaturated`` (the router retries
  elsewhere); a crc or digest mismatch raises the typed
  :class:`KVChainCorrupt` (**PT-SRV-007**) — corrupt bytes never touch an
  engine.
- :class:`TieredRouter` — a :class:`~paddle_tpu.inference.fleet.FleetRouter`
  whose replicas are partitioned into a PREFILL tier (new submissions
  route here; pack prompts at full batch width) and a DECODE tier: at
  prefill-complete (first token scheduled) the chain migrates to the
  least-loaded decode replica, which resumes decode at the recorded
  position. Sample keys are stateless (``fold_in(seed, position)``) and
  the spliced pages are byte-identical, so the continued stream is
  **byte-identical** (greedy and seeded) to a single-replica run.
- Crash safety — the handoff is journaled on both sides: the source
  appends ``migr-kv`` (with the chain digest) so its failover never
  re-serves the rid, and the destination journals the admit + delivered
  high-water mark so ITS failover re-runs prefill and verifies the
  delivered prefix byte-for-byte (PT-SRV-005). Mid-migration
  engine/replica faults therefore either re-run prefill or re-splice —
  never double-serve — riding the existing
  ``ServingSupervisor``/``RequestJournal`` machinery. The ordering is
  deliberately at-most-once: a whole-process crash in the brief window
  between the two journal writes drops the rid on restart rather than
  risking the admit-first ordering's double-serve.

Failure edges (docs/SERVING.md "Disaggregated tiers" state machine):

====================  ===================================================
pool/slot shortfall   ``EngineSaturated`` at import → retry the next
                      decode replica → fall back to re-running prefill
                      under resume semantics (never refused)
corrupt in transit    ``KVChainCorrupt`` (PT-SRV-007) → prefill re-run on
                      the decode side, delivered prefix verified — the
                      ``kv_migration_corruption`` drill
decode replica dies   journal-backed failover (PT-FLT-001): re-runs
                      prefill on a survivor, verifies, streams on
prefill replica dies  its journal's ``migr-kv`` records keep migrated
                      rids out of the replay set — no double service
no decode tier left   candidates stay on the prefill tier and decode in
                      place (tiers are an optimization, not a capability
                      split)
====================  ===================================================

Observability: every successful handoff stamps a ``migrate`` span on the
request's trace lane and feeds the ``pt_migration_*`` counter/histogram
families (observability/tracing.py; REQUIRED by ``tools/scrape_metrics.py
--selftest``); router-level stats ride ``pt_fleet_*`` via the fleet
collector. ``bench.py bench_disagg`` A/Bs a unified fleet against a
1-prefill+1-decode tier under the bursty open-loop schedule
(``serving_disagg_ttft_p99_under_burst_ms`` /
``serving_kv_migration_time_s``, both SECONDARY-guarded).
"""

from __future__ import annotations

import hashlib
import json
import time
import zlib
from typing import Callable, List, Optional, Set

import numpy as np

from ..ops.paged_attention import (gather_chain_pages, gather_chain_scales,
                                   logical_page_shape, pool_geometry,
                                   require_kv_layers, scatter_chain_pages)
from .fleet import FleetRouter, ReplicaState, _Replica
from .recovery import _admit_record, _request_from
from .serving import ContinuousBatchingEngine, EngineSaturated, Request

__all__ = ["KVChainCodec", "KVChainCorrupt", "TieredRouter"]


class KVChainCorrupt(RuntimeError):
    """PT-SRV-007: a migrated KV-chain artifact failed its per-page crc32,
    its chain digest, or structural validation — the bytes were damaged in
    transit. The splice is refused with the destination engine untouched;
    the router re-runs prefill on the decode side instead (the delivered
    prefix is then regenerated and verified byte-for-byte)."""


class KVChainCodec:
    """Serialize / splice a slot's finished-prefill KV state.

    Artifact layout (self-describing, version-tagged)::

        b"PTKV1" + <8-hex header length> + <header json> + <page payload>

    The header carries the full admit record (prompt ids, sampling key
    state — seed/temperature/top-p/top-k — deadline, priority, tenant),
    the absolute resume position, the delivered token ids, the pool
    geometry (layers, kv heads, page size, head dim, dtype), the chain
    shape (``n_blocks`` total, ``n_written`` pages of real k/v), a crc32
    per written page (over every layer's k+v bytes for that page) and a
    blake2b chain digest over the canonical digest-less header + the
    payload — header fields (delivered tokens, sampling key state) are
    integrity-protected exactly like the page bytes. The payload is
    each layer's k then v pages for the written prefix of the chain, in
    block-table order.

    ``verify_crc=False`` is the fault drill's control arm ONLY: it splices
    whatever bytes arrive, demonstrating the silent stream corruption the
    verification exists to prevent. Never disable it in production.
    """

    MAGIC = b"PTKV1"

    def __init__(self, verify_crc: bool = True):
        self.verify_crc = bool(verify_crc)

    # -- export ------------------------------------------------------------
    def export_chain(self, engine: ContinuousBatchingEngine,
                     rid: int) -> bytes:
        """Serialize ``rid``'s slot state from a prefix-cache engine. The
        slot must be DECODING (prefill complete, >= 1 token scheduled);
        the source engine is not disturbed — callers release the slot
        (``withdraw_active``) only after the bytes are safely out."""
        if engine.prefix_cache is None:
            raise ValueError("KV-chain export needs a prefix-cache engine")
        engine._refuse_over_groups(
            ("chain export (KVChainCodec: PTKV1 carries one chain of pages "
             "a layer)", True))
        slot = engine.slot_of(rid)
        if slot is None:
            raise KeyError(f"rid {rid} holds no active slot")
        req = engine._slots[slot]
        engine._drain_pending()
        if req._n_out < 1 or len(req.output) < req._n_out:
            raise RuntimeError(
                f"rid {rid}: export before the first token materialized "
                f"({len(req.output)}/{req._n_out})")
        pos = int(engine._pos[slot])
        page = engine.page_size
        blocks = list(engine._slot_blocks[slot])
        n_cached = pos - 1                  # tokens already in the cache
        n_written = -(-n_cached // page)
        kv = engine.caches["kv"]
        # the artifact keeps the logical [kv_heads, page, head_dim] order,
        # whatever form the pools are stored in
        pages = gather_chain_pages(kv, blocks[:n_written],
                                   head_dim=engine.model.config.head_dim)
        # int8 block format: the payload is the RAW int8 page bytes (crc
        # covers them exactly as stored); the per-block dequant scales ride
        # the header, integrity-protected by the chain digest like every
        # other header field
        scales = gather_chain_scales(kv, blocks[:n_written])
        kvh, _, hd = pages[0][0].shape[1:]
        dtype = np.asarray(pages[0][0]).dtype
        # serialize each side ONCE; the per-page crcs are computed over
        # offsets into those bytes (mirroring _verify's layout walk) —
        # chains run to tens of MB at production shapes, so a second
        # .tobytes() pass would double the handoff's memcpy cost
        page_bytes = int(kvh) * page * int(hd) * dtype.itemsize
        pieces: List[bytes] = []
        for pk, pv in pages:
            pieces.append(pk.tobytes())
            pieces.append(pv.tobytes())
        page_crc: List[int] = []
        for j in range(n_written):
            crc = 0
            for side in pieces:
                off = j * page_bytes
                crc = zlib.crc32(side[off:off + page_bytes], crc)
            page_crc.append(crc & 0xFFFFFFFF)
        hdr = dict(_admit_record(req))
        hdr.update(v=1, pos=pos,
                   delivered=[int(t) for t in req.output],
                   page_size=page, layers=len(kv), kvh=int(kvh),
                   hd=int(hd), dtype=str(dtype), n_blocks=len(blocks),
                   n_written=n_written, page_crc=page_crc)
        if scales is not None:
            hdr["kv_scales"] = [[np.asarray(s, np.float32).tolist()
                                 for s in pair] for pair in scales]
        # the chain digest covers the CANONICAL header (digest-excluded) +
        # every payload byte: a transit flip anywhere — a delivered token
        # id, the seed, a sampling knob, a page — is a PT-SRV-007
        # rejection, not a silently-diverging resumed stream
        hdr["digest"] = self._digest(hdr, pieces)
        hj = json.dumps(hdr, separators=(",", ":")).encode("utf-8")
        return self.MAGIC + (b"%08x" % len(hj)) + hj + b"".join(pieces)

    @staticmethod
    def _digest(hdr: dict, payload_parts) -> str:
        """blake2b over the canonical (sorted-keys, digest-less) header
        json + the payload bytes — export and verify share this so the
        wire header's json round trip cannot skew the comparison."""
        probe = {k: v for k, v in hdr.items() if k != "digest"}
        dig = hashlib.blake2b(digest_size=16)
        dig.update(json.dumps(probe, sort_keys=True,
                              separators=(",", ":")).encode("utf-8"))
        for part in payload_parts:
            dig.update(part)
        return dig.hexdigest()

    # -- parsing / verification -------------------------------------------
    def peek(self, artifact: bytes) -> dict:
        """Header only (structural validation, no crc work)."""
        return self._parse(artifact)[0]

    def _parse(self, artifact):
        """Split an artifact into (header dict, payload view). The payload
        stays a zero-copy memoryview — chains run to tens of MB, and this
        runs once for ``peek`` plus once per import attempt; crc32,
        blake2b and np.frombuffer all consume the view directly."""
        m = len(self.MAGIC)
        if not isinstance(artifact, (bytes, bytearray, memoryview)):
            raise KVChainCorrupt(
                "PT-SRV-007: not a KV-chain artifact (bad magic)")
        mv = memoryview(artifact)
        if len(mv) < m + 8 or bytes(mv[:m]) != self.MAGIC:
            raise KVChainCorrupt(
                "PT-SRV-007: not a KV-chain artifact (bad magic)")
        try:
            hlen = int(bytes(mv[m:m + 8]), 16)
        except ValueError:
            raise KVChainCorrupt(
                "PT-SRV-007: malformed header length") from None
        if hlen <= 0 or m + 8 + hlen > len(mv):
            raise KVChainCorrupt("PT-SRV-007: header length out of range")
        try:
            hdr = json.loads(bytes(mv[m + 8:m + 8 + hlen]).decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError):
            raise KVChainCorrupt(
                "PT-SRV-007: undecodable artifact header") from None
        payload = mv[m + 8 + hlen:]
        try:
            itemsize = np.dtype(hdr["dtype"]).itemsize
            expect = (hdr["layers"] * 2 * hdr["n_written"] * hdr["kvh"]
                      * hdr["page_size"] * hdr["hd"] * itemsize)
            if not hdr["delivered"] or hdr["n_written"] < 1:
                raise KVChainCorrupt(
                    "PT-SRV-007: artifact carries no finished prefill")
        except (KeyError, TypeError, ValueError):
            raise KVChainCorrupt(
                "PT-SRV-007: artifact header missing chain fields") from None
        if len(payload) != expect:
            raise KVChainCorrupt(
                f"PT-SRV-007: payload is {len(payload)} bytes, header "
                f"promises {expect}")
        return hdr, payload

    def _verify(self, hdr: dict, payload: bytes) -> None:
        """Per-page crc32 + chain digest — names the damaged page."""
        itemsize = np.dtype(hdr["dtype"]).itemsize
        page_bytes = hdr["kvh"] * hdr["page_size"] * hdr["hd"] * itemsize
        side_bytes = hdr["n_written"] * page_bytes
        crcs = list(hdr.get("page_crc") or ())
        if len(crcs) != hdr["n_written"]:
            raise KVChainCorrupt(
                "PT-SRV-007: per-page crc table does not cover the chain")
        for j in range(hdr["n_written"]):
            crc = 0
            for layer in range(hdr["layers"]):
                base = layer * 2 * side_bytes
                for side in range(2):
                    off = base + side * side_bytes + j * page_bytes
                    crc = zlib.crc32(payload[off:off + page_bytes], crc)
            if (crc & 0xFFFFFFFF) != crcs[j]:
                raise KVChainCorrupt(
                    f"PT-SRV-007: chain page {j} failed its crc32 — "
                    f"rid={hdr.get('rid')} artifact corrupted in transit")
        if self._digest(hdr, (payload,)) != hdr.get("digest"):
            raise KVChainCorrupt(
                f"PT-SRV-007: chain digest mismatch — rid={hdr.get('rid')} "
                "header (prompt/delivered/sampling state) and pages must "
                "arrive exactly as exported")

    def _unpack(self, hdr: dict, payload: bytes):
        dt = np.dtype(hdr["dtype"])
        shape = (hdr["n_written"], hdr["kvh"], hdr["page_size"], hdr["hd"])
        n = int(np.prod(shape))
        nb = n * dt.itemsize
        out, off = [], 0
        for _ in range(hdr["layers"]):
            k = np.frombuffer(payload, dt, n, off).reshape(shape)
            off += nb
            v = np.frombuffer(payload, dt, n, off).reshape(shape)
            off += nb
            out.append((k, v))
        return out

    # -- import ------------------------------------------------------------
    def import_chain(self, engine: ContinuousBatchingEngine,
                     artifact: bytes,
                     req: Optional[Request] = None) -> Request:
        """Splice a chain into ``engine``: verify (unless the drill's
        control arm disabled it), allocate ``n_blocks`` fresh pages
        (LRU-evicting idle cached blocks on shortfall), scatter the
        written page bytes, and resume the request at the recorded
        position via ``admit_migrated`` (radix-inserted, refcounts
        correct). Raises ``EngineSaturated`` on slot/pool shortfall with
        the engine untouched, :class:`KVChainCorrupt` on damage."""
        hdr, payload = self._parse(artifact)
        if self.verify_crc:
            self._verify(hdr, payload)
        if engine.prefix_cache is None:
            raise ValueError("KV-chain splice needs a prefix-cache engine")
        engine._refuse_over_groups(
            ("the KV-chain splice (import_chain: PTKV1 carries one chain of "
             "pages a layer)", True))
        kv = engine.caches["kv"]
        require_kv_layers(kv, "the KV-chain splice (import_chain)")
        # the chain is in the logical order; a lane-dense pool folds it on
        # the way in (scatter_chain_pages)
        pool_shape = logical_page_shape(kv[0][0], hdr["hd"])
        want = (hdr["kvh"], hdr["page_size"], hdr["hd"])
        if (engine.page_size != hdr["page_size"] or len(kv) != hdr["layers"]
                or pool_shape != want
                or str(kv[0][0].dtype) != hdr["dtype"]):
            raise ValueError(
                f"destination pool geometry {len(kv)}x{pool_shape} "
                f"({kv[0][0].dtype}) cannot hold chain "
                f"{hdr['layers']}x{want} ({hdr['dtype']}) — tiers must "
                "share the serving config")
        if engine._maxp < hdr["n_blocks"]:
            raise ValueError(
                f"chain spans {hdr['n_blocks']} pages but the destination "
                f"table holds {engine._maxp} per slot")
        if not engine._free_slots:
            raise EngineSaturated(
                f"no free slot on splice target for rid={hdr['rid']}")
        scales = None
        if hdr["dtype"] == "int8":
            # validated BEFORE any allocator state moves: a structurally
            # damaged scale table refuses the splice with the engine
            # untouched, like every other PT-SRV-007 path
            raw = hdr.get("kv_scales")
            if (not isinstance(raw, list) or len(raw) != hdr["layers"]
                    or any(len(pair) != 2 for pair in raw)):
                raise KVChainCorrupt(
                    "PT-SRV-007: int8 chain without a per-layer "
                    "kv_scales table — the block format needs its dequant "
                    "scales to travel with the page bytes")
            scales = [tuple(np.asarray(s, np.float32) for s in pair)
                      for pair in raw]
        blocks = engine._alloc.alloc(hdr["n_blocks"],
                                     evict=engine._radix.evict_lru)
        if blocks is None:
            raise EngineSaturated(
                f"splice pool shortfall for rid={hdr['rid']}: chain needs "
                f"{hdr['n_blocks']} blocks, {engine._alloc.free_blocks} "
                "free after LRU eviction — retry another decode replica")
        try:
            engine.caches = {
                "kv": scatter_chain_pages(kv, blocks[:hdr["n_written"]],
                                          self._unpack(hdr, payload),
                                          scales=scales),
                "tables": engine.caches["tables"]}
            if req is None:
                req = _request_from(hdr)
                req.output = [int(t) for t in hdr["delivered"]]
                req._n_out = len(req.output)
            engine.admit_migrated(req, blocks, hdr["pos"],
                                  last_tok=int(hdr["delivered"][-1]))
        except Exception:
            engine._alloc.decref(blocks)
            raise
        return req


class TieredRouter(FleetRouter):
    """Disaggregated prefill/decode tiers over the fleet substrate.

    >>> tiered = TieredRouter(build_prefill, build_decode, fleet_dir,
    ...                       num_prefill=1, num_decode=2)
    >>> tiered.submit(Request(prompt, max_new_tokens=64))
    >>> done = tiered.run_until_done()

    Replicas ``0..num_prefill-1`` form the prefill tier (new submissions
    route only here — pack prompts at full batch width by building the
    prefill engine fused with a generous ``pack_rows``), the rest the
    decode tier. After every fleet tick the router scans the prefill tier
    for finished prefills and migrates each chain to the least-loaded
    decode replica through :class:`KVChainCodec` (module docstring for
    the failure edges). All FleetRouter machinery — journal-backed
    failover, progress heartbeats, drain/rolling restart, brownout
    shedding, the fleet collector — runs unchanged over both tiers.
    """

    def __init__(self, build_prefill: Callable[[], ContinuousBatchingEngine],
                 build_decode: Callable[[], ContinuousBatchingEngine],
                 fleet_dir: str, num_prefill: int = 1, num_decode: int = 1,
                 codec: Optional[KVChainCodec] = None, **kw):
        if num_prefill < 1 or num_decode < 1:
            raise ValueError("each tier needs at least one replica")
        self._build_prefill = build_prefill
        self._build_decode = build_decode
        self._num_prefill = int(num_prefill)
        self.codec = codec if codec is not None else KVChainCodec()
        super().__init__(build_prefill, fleet_dir,
                         num_replicas=int(num_prefill) + int(num_decode),
                         **kw)
        # fail at construction, not on the first finished prefill: both
        # sides of the handoff need dynamic block tables over the
        # refcounted pool (export reads a slot's chain, import splices one)
        for rep in self.replicas:
            if rep.sup.engine.prefix_cache is None:
                raise ValueError(
                    f"{rep.tier}-tier replica {rep.idx} was built without "
                    "a prefix cache — KV-block migration needs "
                    "prefix_cache engines on both tiers")
        # migration_deferred counts STEPS a ready candidate waited for
        # decode capacity/compatibility (pre-check, per step);
        # migration_refused counts actual splice refusals at import (per
        # target tried) — conflating them would read a busy-wait as a
        # refusal storm and mask real splice failures
        self.stats.update(migrations=0, migration_s=0.0, migration_pages=0,
                          migration_bytes=0, migration_corrupt=0,
                          migration_deferred=0, migration_refused=0,
                          migration_reprefill=0, migration_hedges=0)
        #: per-migration wall-clock seconds, newest-last, capped — the
        #: ``serving_migration_under_loss`` bench reads p99 from here
        #: (hedges never fire in-process: no wire, no timeouts — the key
        #: exists so collectors read both pumps uniformly)
        self.migration_samples: List[float] = []
        self._corrupt_hook = None

    # -- tier membership (fleet.py hooks) ----------------------------------
    def _builder(self, idx: int):
        return (self._build_prefill if idx < self._num_prefill
                else self._build_decode)

    def tier_of(self, idx: int) -> str:
        return "prefill" if idx < self._num_prefill else "decode"

    def _routable(self, req: Request) -> List[_Replica]:
        """New submissions take the prefill tier; with no prefill replica
        alive the decode tier absorbs them (tiers are an optimization,
        not a capability split — every engine runs the full path)."""
        alive = super()._routable(req)
        pre = [r for r in alive if r.tier == "prefill"]
        return pre or alive

    def _pick_survivor(self, req: Request,
                       exclude: Set[int] = frozenset()) -> Optional[_Replica]:
        """Failover re-runs prefill, so prefill-tier survivors are
        preferred; once (re)finished it migrates again as usual."""
        alive = [r for r in self.replicas
                 if r.state == ReplicaState.ALIVE and r.idx not in exclude]
        pool = [r for r in alive if r.tier == "prefill"] or alive
        if not pool:
            return None
        n = len(pool)
        return min(pool, key=lambda r: (r.sup.load(),
                                        (r.idx - req.rid) % n))

    # -- the migration pump ------------------------------------------------
    # LOCKSTEP NOTE: procfleet/router.py's ProcTieredRouter mirrors this
    # pump over the wire (export_migration/import_migration replace the
    # direct engine access) — a behavioral fix to either pump must land
    # in BOTH.
    def step(self) -> None:
        super().step()
        self._migrate_ready()

    def _decode_targets(self, rid: int) -> List[_Replica]:
        alive = [r for r in self.replicas
                 if r.state == ReplicaState.ALIVE and r.tier == "decode"]
        n = max(1, len(alive))
        return sorted(alive, key=lambda r: (r.sup.load(),
                                            (r.idx - rid) % n))

    def _migrate_ready(self) -> None:
        """Migrate every finished prefill off the prefill tier. Runs on
        the driver thread after the fleet tick (never inside
        ``parallel_step`` replica threads), so engine state is quiescent."""
        if self._corrupt_hook is None:
            from ..distributed.resilience.faults import corrupt

            self._corrupt_hook = corrupt
        for rep in self.replicas:
            if rep.state != ReplicaState.ALIVE or rep.tier != "prefill":
                continue
            for rid in rep.sup.engine.migration_ready():
                user = self.requests.get(rid)
                if (user is None or user.done
                        or rep.sup._live.get(rid) is None):
                    continue
                if rid in rep.sup._verify:
                    # recovery catch-up twin: let it reach and verify the
                    # delivered mark locally before its chain travels
                    continue
                self._migrate_one(rep, rid, user)

    def _compatible(self, src_engine, dst_engine, user: Request,
                    need: int) -> bool:
        """Pool-geometry + capacity gate, checked BEFORE ownership moves:
        a chain must never be retired from its source toward a destination
        that cannot hold it (mismatched tier configs would otherwise
        strand the request after the ``migr-kv`` handoff)."""
        if (dst_engine.prefix_cache is None
                or dst_engine.page_size != src_engine.page_size
                or dst_engine._maxp < need
                or len(user.prompt) + user.max_new_tokens
                > dst_engine.max_len):
            return False
        src_kv, dst_kv = src_engine.caches["kv"], dst_engine.caches["kv"]
        if pool_geometry(dst_kv) != pool_geometry(src_kv):
            return False
        # capacity: free + radix-registered is an optimistic pool estimate
        # (registered blocks may be pinned by live tables), so the
        # import's EngineSaturated fallback stays load-bearing
        return bool(dst_engine._free_slots) and (
            dst_engine._alloc.free_blocks
            + len(dst_engine._radix)) >= need

    def _migrate_one(self, src: _Replica, rid: int, user: Request) -> bool:
        # compatibility/capacity pre-check BEFORE ownership moves: a tier
        # that is merely full (or misconfigured) is not a failure — the
        # candidate keeps decoding on the prefill tier and retries next
        # step.
        need = src.sup.engine._pages_needed(len(user.prompt),
                                            user.max_new_tokens)
        targets = [r for r in self._decode_targets(rid)
                   if self._compatible(src.sup.engine, r.sup.engine, user,
                                       need)]
        if not targets:
            self.stats["migration_deferred"] += 1
            return False            # no capacity / no decode tier alive:
        #                             decode in place, retry next step
        t0 = time.monotonic()
        t0_tr = None if self.tracer is None else self.tracer.now()
        # flush-before-surface: everything delivered so far is journaled
        # and spliced into the caller's object before the chain travels
        src.sup._sync_progress()
        twin = src.sup._live.get(rid)
        if twin is None or twin.done:
            return False            # finished inside that sync
        art = self.codec.export_chain(src.sup.engine, rid)
        hdr = self.codec.peek(art)
        # in-transit hook: the kv_migration_corruption drill flips page
        # bytes here (FaultPlan site ``serving.kv_transfer``)
        art = self._corrupt_hook("serving.kv_transfer", f"rid:{rid}", art)
        # ownership leaves the prefill journal BEFORE the splice lands
        # (``migr-kv`` + slot release): an ENGINE/replica fault on either
        # side now re-runs prefill from the decode admit or this router's
        # resume fallback — the rid is never served twice. This is
        # deliberately at-most-once: a whole-PROCESS crash inside the
        # journal-to-journal window would drop the rid on restart (neither
        # journal replays it), which streams-wise beats the admit-first
        # ordering's double-serve window.
        src.sup.retire_migrated(rid, hdr["digest"])
        placed = None
        corrupt_art = False
        for rep in targets:
            try:
                rep.sup.submit_migrated(user, art, self.codec)
                placed = rep
                break
            except KVChainCorrupt as e:
                # PT-SRV-007 takes the same retry-elsewhere arm as a
                # refusal (UNIFIED policy, mirrored in the proc pump where
                # wire-transit damage really is per-hop); in-process the
                # bytes are shared so later targets will refuse them too,
                # ending in the reprefill fallback below either way
                corrupt_art = True
                self.stats["migration_corrupt"] += 1
                self.events.append(("PT-SRV-007", str(e)))
                if self.tracer is not None:
                    self.tracer.migration_failure(
                        rid, "corrupt", tags={"replica": rep.idx})
                continue
            except (EngineSaturated, ValueError):
                # saturated at import (the pre-check's pool estimate was
                # optimistic) — or a geometry refusal the pre-check
                # somehow missed: either way this target is out, the
                # bytes are fine, try the next one
                self.stats["migration_refused"] += 1
                if self.tracer is not None:
                    self.tracer.migration_failure(
                        rid, "refused", tags={"replica": rep.idx})
                continue
            except Exception as e:  # noqa: BLE001 — replica death boundary
                # an unexpected splice failure (device OOM, journal IO)
                # leaves that replica's engine untrusted — same posture as
                # _step_all: mark it dead and fail its work over. Must not
                # escape: the rid is already retired from the source, so
                # an unhandled raise here would strand it forever.
                self._mark_dead(rep, f"splice of rid={rid} raised "
                               f"{type(e).__name__}: {e}")
                self._handle_death(rep)
                if self._assigned.get(rid, src.idx) != src.idx:
                    # the replica had journaled the admit before dying —
                    # its failover already re-placed the rid
                    return True
                continue
        if placed is None:
            # every decode replica refused (or the artifact is corrupt):
            # re-run prefill under resume semantics on the least-loaded
            # surviving replica (decode tier first) — journaled work is
            # never refused, and the delivered prefix is regenerated +
            # verified byte-for-byte (PT-SRV-005) before anything new
            # streams
            alive = self._decode_targets(rid)     # re-query: a target may
            target = (alive[0] if alive           # have died in the loop
                      else self._pick_survivor(user, exclude=set()))
            if target is None:
                user.done = user.failed = True
                user.error = (f"PT-TIER-001: no surviving replica to "
                              f"place migrated rid={rid} on")
                self._trace_lost(rid, user, src.idx)
                return True
            self.stats["migration_reprefill"] += 1
            target.sup.submit(user, resume=True)
            self._assigned[rid] = target.idx
            self.events.append(
                ("PT-TIER-001",
                 f"rid={rid} chain not spliced "
                 f"({'corrupt' if corrupt_art else 'refused'}) — prefill "
                 f"re-run on replica {target.idx}"))
            return True
        self._assigned[rid] = placed.idx
        dt = time.monotonic() - t0
        self.stats["migrations"] += 1
        self.stats["migration_s"] += dt
        self.migration_samples.append(dt)
        del self.migration_samples[:-512]
        self.stats["migration_pages"] += int(hdr["n_written"])
        self.stats["migration_bytes"] += len(art)
        self.events.append(
            ("PT-TIER-001",
             f"rid={rid} chain ({hdr['n_written']} page(s), {len(art)} "
             f"bytes) migrated replica {src.idx} -> {placed.idx} in "
             f"{dt * 1e3:.1f}ms"))
        if self.tracer is not None:
            self.tracer.migrate(rid, src.idx, placed.idx,
                                pages=int(hdr["n_written"]),
                                nbytes=len(art), t0=t0_tr,
                                tags={"replica": placed.idx})
        return True
