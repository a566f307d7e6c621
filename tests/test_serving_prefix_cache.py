"""Prefix cache over the paged-KV block pool + chunked prefill
(inference/serving.py, ops/paged_attention.py — docs/SERVING.md).

Covers the block lifecycle (alloc -> share -> COW -> evict), the
warm-vs-cold token bit-identity guarantee (greedy AND seeded sampling,
including across a copy-on-write divergence point), chunked-prefill
correctness while other slots decode, deadline eviction decref'ing (not
freeing) shared blocks, seeded pool exhaustion backpressure, and the
bounded compile-cache telemetry.
"""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          PrefixCacheConfig, Request)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.paged_attention import BlockAllocator, RadixPrefixCache


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def eng(model):
    """ONE shared cache-enabled engine: programs compile once for the whole
    module; tests use distinct prompts so cache state composes."""
    _, m = model
    return ContinuousBatchingEngine(
        m, max_batch=2, max_len=64, page_size=8,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16))


@pytest.fixture(scope="module")
def eng2(model):
    """Shared small-block engine (chunked prefill + deadline tests): one
    compile set for both — tier-1 budget. A pack of TWO 8-token rows
    (``_run_pack``'s budget is max(mid-prefill slots, pack_rows)), so a
    24-token prompt is longer than one pack and stays mid-prefill for a
    step."""
    _, m = model
    return ContinuousBatchingEngine(
        m, max_batch=2, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8, pack_rows=2))


def _drain(e):
    """Leave the module-scoped engine as the next test expects it: nothing
    in flight, no cached chain. Asserts nothing: it runs in ``finally``,
    where a failed check would hide the test's own failure."""
    e.run_until_done(max_steps=300)
    e._radix.evict_lru(e._alloc.num_blocks)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


def _ref(m, prompt, n):
    # max_length pins the KV bucket so every reference call in the module
    # reuses ONE compiled decode-block program (tier-1 budget)
    out = m.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                     max_new_tokens=n, temperature=0.0,
                     max_length=32).numpy()[0]
    return [int(t) for t in out]


def _serve(e, prompt, n, **kw):
    r = Request(prompt, max_new_tokens=n, **kw)
    e.add_request(r)
    e.run_until_done(max_steps=500)
    return r


# ---------------------------------------------------------------------------
# host-side bookkeeping units
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_alloc_refcount_free_cycle(self):
        a = BlockAllocator(4)
        got = a.alloc(3)
        assert len(got) == 3 and a.free_blocks == 1
        a.incref([got[0]])
        a.decref([got[0]])
        assert a.refcount(got[0]) == 1     # still owned by the allocator ref
        a.decref(got)
        assert a.free_blocks == 4
        with pytest.raises(RuntimeError, match="double free"):
            a.decref([got[0]])

    def test_exhaustion_returns_none_never_overcommits(self):
        a = BlockAllocator(2)
        assert a.alloc(2) is not None
        assert a.alloc(1) is None

    def test_hold_models_pool_exhaustion(self):
        a = BlockAllocator(4)
        assert a.hold(3) == 3
        assert a.alloc(2) is None
        assert a.release_held() == 3
        assert a.alloc(2) is not None

    def test_cached_idle_blocks_stay_out_of_free_list(self):
        a = BlockAllocator(2)
        cached = set()
        a.is_cached = cached.__contains__
        (b0, b1) = a.alloc(2)
        cached.add(b0)
        a.decref([b0, b1])
        assert a.free_blocks == 1          # b0 retained for the cache
        a.incref([b0])                     # prefix hit revives it
        assert a.refcount(b0) == 1


class TestRadixPrefixCache:
    def test_match_insert_longest_prefix(self):
        a = BlockAllocator(8)
        rx = RadixPrefixCache(4, a)
        toks = np.arange(12, dtype=np.int32)
        blocks = a.alloc(3)
        rx.insert(toks, blocks)
        assert rx.match(toks) == blocks
        assert rx.match(toks[:8]) == blocks[:2]
        # divergent tail: only the common full blocks match
        other = np.concatenate([toks[:8], np.full(4, 99, np.int32)])
        assert rx.match(other) == blocks[:2]
        assert rx.match(np.full(4, 77, np.int32)) == []

    def test_evict_lru_leaf_first_respects_refcounts(self):
        a = BlockAllocator(8)
        rx = RadixPrefixCache(4, a)
        toks = np.arange(8, dtype=np.int32)
        blocks = a.alloc(2)
        rx.insert(toks, blocks)
        # parent still referenced by a live request, child idle
        a.decref([blocks[1]])
        assert rx.evict_lru(2) == 1        # only the idle LEAF goes
        assert not rx.has_block(blocks[1]) and rx.has_block(blocks[0])
        a.decref([blocks[0]])
        assert rx.evict_lru(1) == 1        # parent became an evictable leaf
        assert a.free_blocks == 8

    def test_first_writer_wins_on_duplicate_insert(self):
        a = BlockAllocator(8)
        rx = RadixPrefixCache(4, a)
        toks = np.arange(4, dtype=np.int32)
        b1 = a.alloc(1)
        b2 = a.alloc(1)
        assert rx.insert(toks, b1) == b1
        assert rx.insert(toks, b2) == []   # duplicate stays private
        assert rx.match(toks) == b1


# ---------------------------------------------------------------------------
# warm == cold bit-identity (the acceptance guarantee)
# ---------------------------------------------------------------------------

def test_warm_equals_cold_greedy_and_matches_generate(model, eng):
    cfg, m = model
    p = _prompt(cfg, 12, 100)
    ref = _ref(m, p, 6)
    cold = _serve(eng, p, 6)
    assert eng.stats["miss_tokens"] >= 12
    warm = _serve(eng, p, 6)
    assert cold.tokens == ref            # semantic correctness
    assert warm.tokens == cold.tokens    # bit-identical token stream
    assert eng.stats["hit_tokens"] >= 8  # full blocks of the prompt hit


def test_warm_equals_cold_seeded_sampling(model, eng):
    cfg, _ = model
    p = _prompt(cfg, 12, 101)
    kw = dict(temperature=0.8, top_p=0.9, seed=1234)
    cold = _serve(eng, p, 6, **kw)
    warm = _serve(eng, p, 6, **kw)
    assert warm.tokens == cold.tokens


def test_warm_equals_cold_across_cow_divergence(model, eng):
    """Full-prompt hit (prompt length a page multiple) forces copy-on-write
    of the last shared block before the first-token re-step; the COW'd
    request must emit the cold stream bit-for-bit, and a divergent sampled
    continuation must leave the shared blocks intact for a THIRD request."""
    cfg, m = model
    p = _prompt(cfg, 16, 102)            # 2 full pages -> full-match COW
    ref = _ref(m, p, 5)
    cold = _serve(eng, p, 5)
    cows = eng.stats["cow_copies"]
    warm = _serve(eng, p, 5)
    assert eng.stats["cow_copies"] > cows
    assert cold.tokens == ref and warm.tokens == cold.tokens
    # divergence: a sampled continuation writes different decode tokens
    _serve(eng, p, 5, temperature=1.2, seed=7)
    # the shared prefix blocks survived both the COW and the divergence
    again = _serve(eng, p, 5)
    assert again.tokens == ref


def test_shared_system_prompt_partial_hits(model, eng):
    cfg, m = model
    sys_p = _prompt(cfg, 16, 103)
    hits0 = eng.stats["hit_tokens"]
    tails = [_prompt(cfg, 5, 104 + i) for i in range(3)]
    for tail in tails:
        p = np.concatenate([sys_p, tail])
        r = _serve(eng, p, 4)
        assert r.tokens == _ref(m, p, 4)
    # requests 2 and 3 hit the 16-token system prefix
    assert eng.stats["hit_tokens"] >= hits0 + 32


@pytest.mark.slow
def test_prefix_cache_fresh_engine_determinism(model):
    """A fresh engine's cold stream equals another fresh engine's warm
    stream — nothing about cache state leaks into token values."""
    cfg, m = model
    p = _prompt(cfg, 12, 106)
    e1 = ContinuousBatchingEngine(m, max_batch=1, max_len=32, page_size=8,
                                  prefix_cache=True)
    cold = _serve(e1, p, 4)
    e2 = ContinuousBatchingEngine(m, max_batch=1, max_len=32, page_size=8,
                                  prefix_cache=True)
    _serve(e2, p, 4)                     # prime
    warm = _serve(e2, p, 4)
    assert warm.tokens == cold.tokens


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_interleaves_with_decode(model, eng2):
    """A long admit advances one pack per step while an active slot keeps
    decoding — and both streams match single-request generate()."""
    cfg, m = model
    e = eng2
    long_p = _prompt(cfg, 24, 107)
    short_p = _prompt(cfg, 6, 108)
    rs = Request(short_p, max_new_tokens=8)
    e.add_request(rs)
    e.step()                              # short admitted and decoding
    rl = Request(long_p, max_new_tokens=4)
    e.add_request(rl)
    e.step()                              # long admitted: ONE pack only,
    #                                       two rows of 8 of its 24 tokens
    assert list(e._prefill_next.values()) == [16]
    assert not rs.done                    # decode goes on beside it
    decoded_mid_prefill = rs._n_out
    e.run_until_done(max_steps=300)
    assert rs._n_out > decoded_mid_prefill or rs.done
    assert rs.tokens == _ref(m, short_p, 8)
    assert rl.tokens == _ref(m, long_p, 4)


# ---------------------------------------------------------------------------
# block lifecycle under eviction / exhaustion
# ---------------------------------------------------------------------------

def test_deadline_eviction_decrefs_not_frees_shared_blocks(model, eng2):
    """Regression (satellite): an evicted request sharing a prefix with a
    live one must DECREF the shared blocks — the survivor's tokens are
    unchanged."""
    cfg, m = model
    e = eng2
    shared = _prompt(cfg, 16, 109)
    pA = np.concatenate([shared, _prompt(cfg, 4, 110)])
    pB = np.concatenate([shared, _prompt(cfg, 5, 111)])
    refA = _ref(m, pA, 12)
    try:
        rA = Request(pA, max_new_tokens=12)
        e.add_request(rA)
        for _ in range(10):               # A chunk-prefills; its prompt
            e.step()                      # blocks register at first token
            if rA._n_out:
                break
        assert rA._n_out and not rA.done
        hits0 = e.stats["hit_tokens"]
        rB = Request(pB, max_new_tokens=11, deadline_s=0.05)
        # exercise EVICTION, not submit shedding: on a slow machine the
        # feasibility shedder would refuse the doomed deadline at submit
        # (that path has its own tests)
        e.shed_infeasible = False
        try:
            e.add_request(rB)
        finally:
            e.shed_infeasible = True
        e.step()                          # B admitted sharing A's prefix
        assert e.stats["hit_tokens"] - hits0 >= 16   # the share is real
        time.sleep(0.1)
        e.run_until_done(max_steps=300)
        assert rB.failed and rB.done and "deadline" in rB.error
        assert rA.done and not rA.failed
        assert rA.tokens == refA          # survivor undisturbed
        _drain(e)
        assert e._alloc.free_blocks == e._alloc.num_blocks  # no page leaked
    finally:
        _drain(e)


def test_deadline_eviction_mid_chunked_prefill_releases_pages(model, eng2):
    """Regression (satellite): a slot evicted MID-chunked-prefill must
    release its parked/partial pages (all pages allocate at admission;
    eviction before the prompt finishes prefilling returns every one) and
    leave the prefill group — without disturbing the decoding row or the
    next admission into the freed slot."""
    cfg, m = model
    e = eng2
    try:
        # start from a drained pool: leftover cached chains (or a request a
        # failed neighbour left in flight) would make the conservation
        # check depend on test history
        _drain(e)
        assert e._alloc.free_blocks == e._alloc.num_blocks
        # deterministic eviction: the feasibility shedder would refuse the
        # doomed deadline at submit on a warm engine (that path has its
        # own tests); this test needs the request ADMITTED so eviction
        # can bite
        e.shed_infeasible = False
        pa, pb, pc = _prompt(cfg, 6, 130), _prompt(cfg, 24, 131), \
            _prompt(cfg, 6, 132)
        refA = _ref(m, pa, 26)
        rA = Request(pa, max_new_tokens=26)
        e.add_request(rA)
        e.step()                          # A decoding (4 pages)
        rB = Request(pb, max_new_tokens=4, deadline_s=0.25)
        e.add_request(rB)
        e.step()                          # B admitted: ONE pack prefilled
        slot_b = next(iter(e._prefill_next))
        assert e._prefill_next[slot_b] == 16 < len(pb)   # mid-prefill
        blocks_b = list(e._slot_blocks[slot_b])    # all 4 pages parked
        assert len(blocks_b) == 4 and e._alloc.free_blocks == 0
        time.sleep(0.3)
        e.step()                          # deadline tick evicts B
        assert rB.failed and rB.done and "deadline" in rB.error
        assert slot_b not in e._prefill_next       # out of the prefill group
        assert e._slots[slot_b] is None
        # the release rode the step's traced scatter: row parked and
        # inactive on the device (there is no host table)
        assert slot_b not in e._upd
        assert (np.asarray(e.caches["tables"])[slot_b] == e._park).all()
        assert not np.asarray(e._dev_act)[slot_b]
        # every parked/partial page back in the pool — B never registered,
        # so nothing may linger cached-idle either
        for b in blocks_b:
            assert e._alloc.refcount(b) == 0
        assert e._alloc.free_blocks >= len(blocks_b)
        rC = Request(pc, max_new_tokens=4)         # freed slot is reusable
        e.add_request(rC)
        e.run_until_done(max_steps=300)
        assert rA.tokens == refA                   # survivor undisturbed
        assert rC.tokens == _ref(m, pc, 4)
        e._radix.evict_lru(e._alloc.num_blocks)    # A's and C's chains
        assert e._alloc.free_blocks == e._alloc.num_blocks  # no page leaked
    finally:
        e.shed_infeasible = True
        _drain(e)


@pytest.mark.slow   # the fault drill (CI-gated) covers this end-to-end
def test_pool_exhaustion_defers_admission_and_recovers(model):
    """Seeded block-pool exhaustion (FaultPlan 'exhaust'): the queue head
    that cannot get blocks defers — no allocation ever overcommits — and is
    admitted once completed requests release (or LRU-evict) blocks."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec

    cfg, m = model
    e = ContinuousBatchingEngine(m, max_batch=2, max_len=16, page_size=8,
                                 block_size=2, prefix_cache=True)
    pa, pb = _prompt(cfg, 8, 112), _prompt(cfg, 8, 113)
    ra, rb = Request(pa, max_new_tokens=8), Request(pb, max_new_tokens=8)
    plan = FaultPlan(seed=9, specs=[
        FaultSpec("serving.block_pool", "exhaust", at=1, count=1, arg=3)])
    with plan:
        e.add_request(ra)
        e.step()
        e.add_request(rb)
        e.step()                          # rb's allocation is held -> defer
        assert rb._n_out == 0 and len(e._queue) == 1
        e.run_until_done(max_steps=200)
    assert plan.log, "exhaust fault never fired"
    assert ra.tokens == _ref(m, pa, 8)
    assert rb.tokens == _ref(m, pb, 8)   # admitted after blocks released
    assert e.stats["evictions"] >= 1     # rb's alloc LRU-evicted idle cache


def test_matched_blocks_pinned_before_eviction_capable_alloc(model):
    """Regression: admission must incref matched prefix blocks BEFORE the
    eviction-capable alloc. Unpinned, they are refcount-0 CACHED-IDLE and a
    large enough shortfall makes evict_lru reclaim the just-matched chain —
    alloc then hands the same pages back as fresh suffix blocks, double-
    mapping them in the slot's table (decode appends clobber the shared
    prefix k/v). Pinned, the engine defers instead and serves bit-identical
    tokens once blocks are released."""
    cfg, m = model
    e = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                 prefix_cache=True)
    pA = _prompt(cfg, 16, 117)
    pB = np.concatenate([pA[:8], _prompt(cfg, 8, 118)])
    refB = _ref(m, pB, 8)
    rA = Request(pA, max_new_tokens=8)
    e.add_request(rA)
    e.run_until_done(max_steps=200)      # A's 2 prompt blocks now cached
    e._alloc.hold(e._alloc.free_blocks)  # only A's chain is evictable
    rB = Request(pB, max_new_tokens=8)   # matches A's first block; the
    e.add_request(rB)                    # 2-block shortfall exceeds the 1
    e.step()                             # unpinned evictable (A's leaf)
    assert len(e._queue) == 1 and not rB.tokens   # deferred, not admitted
    assert e._radix.match(pA[:8]), "pinned matched chain was evicted"
    for bs in e._slot_blocks:
        assert bs is None or len(set(bs)) == len(bs), \
            f"block double-mapped: {bs}"
    e._alloc.release_held()
    e.run_until_done(max_steps=200)
    assert rB.tokens == refB             # bit-identical once admitted


# ---------------------------------------------------------------------------
# compile-cache bounding (satellite)
# ---------------------------------------------------------------------------

def test_compile_cache_entries_tracked_and_capped(model):
    cfg, m = model
    e = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                 prefix_cache=True, compile_cache_cap=1)
    with pytest.warns(RuntimeWarning, match="PT-TRACE-001"):
        _serve(e, _prompt(cfg, 10, 114), 3)
    assert e.stats["compile_cache_entries"] > 1


def test_compile_cache_quiet_under_cap(model):
    cfg, m = model
    e = ContinuousBatchingEngine(m, max_batch=1, max_len=32, page_size=8)
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        _serve(e, _prompt(cfg, 6, 115), 2)
    assert 0 < e.stats["compile_cache_entries"] <= e.compile_cache_cap


# ---------------------------------------------------------------------------
# second model family
# ---------------------------------------------------------------------------

def test_gpt_prefix_cache_warm_equals_cold():
    from paddle_tpu.models.gpt.modeling import GPTConfig, GPTForCausalLM

    paddle.seed(12)
    cfg = GPTConfig.tiny()
    m = GPTForCausalLM(cfg)
    p = _prompt(cfg, 12, 116)
    ref = _ref(m, p, 4)
    e = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                 prefix_cache=True)
    cold = _serve(e, p, 4)
    warm = _serve(e, p, 4)
    assert cold.tokens == ref and warm.tokens == cold.tokens
    assert e.stats["hit_tokens"] >= 8
