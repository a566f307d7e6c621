"""Crash-recoverable serving (inference/recovery.py — docs/SERVING.md).

Covers the request journal (crc per record, torn-tail tolerance, mid-file
corruption detection), the threaded StepWatchdog, priority admission
ordering, deadline-feasibility shedding (PT-SRV-003) with survivors
byte-identical, supervisor crash recovery with bit-identical replay
(PT-SRV-001), journal survival across a supervisor restart combined with
``max_queue`` backpressure in prefix-cache mode (chunked prefills in
flight), and hysteretic brownout degradation (PT-SRV-006).

The long-wall-clock stall drill (PT-SRV-002 end-to-end) lives in
tools/fault_drill.py and is CI-gated via tests/test_ci_gates.py; here the
watchdog is unit-tested and the stall path slow-marked.
"""

import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.recovery import (JournalCorrupt, RequestJournal,
                                           ServingSupervisor)
from paddle_tpu.inference.serving import (BrownoutConfig,
                                          ContinuousBatchingEngine,
                                          EngineSaturated, PrefixCacheConfig,
                                          Request, RequestShed)
from paddle_tpu.distributed.resilience import (FaultPlan, FaultSpec,
                                               StepWatchdog)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


def _ref(m, prompt, n):
    out = m.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                     max_new_tokens=n, temperature=0.0,
                     max_length=32).numpy()[0]
    return [int(t) for t in out]


# ---------------------------------------------------------------------------
# journal (host-only)
# ---------------------------------------------------------------------------

class TestRequestJournal:
    def test_roundtrip_unfinished_delivered(self, tmp_path):
        p = str(tmp_path / "j.jrnl")
        j = RequestJournal(p)
        j.append("admit", rid=1, prompt=[3, 4], max_new=4, eos=None,
                 temp=0.0, top_p=1.0, top_k=0, seed=1, deadline_s=None,
                 priority=1)
        j.append("prog", rid=1, hwm=2, toks=[7, 8])
        j.append("admit", rid=2, prompt=[5], max_new=2, eos=None,
                 temp=0.0, top_p=1.0, top_k=0, seed=2, deadline_s=None,
                 priority=1)
        j.append("prog", rid=1, hwm=3, toks=[9])
        j.append("fin", rid=2, failed=False)
        j.close()
        recs = RequestJournal.load(p)
        assert [r["k"] for r in recs] == ["admit", "prog", "admit", "prog",
                                          "fin"]
        j2 = RequestJournal(p)
        assert [r["rid"] for r in j2.unfinished()] == [1]
        assert j2.delivered(1) == [7, 8, 9]     # concatenated prog deltas
        j2.close()

    def test_torn_tail_tolerated_and_truncated(self, tmp_path):
        p = str(tmp_path / "j.jrnl")
        j = RequestJournal(p)
        j.append("admit", rid=1, prompt=[1], max_new=1, eos=None, temp=0.0,
                 top_p=1.0, top_k=0, seed=1, deadline_s=None, priority=1)
        j.close()
        with open(p, "ab") as f:                # crash mid-append: torn tail
            f.write(b"deadbeef {\"k\": \"pro")
        j2 = RequestJournal(p)                  # tolerated + truncated away
        assert [r["k"] for r in j2.records] == ["admit"]
        j2.append("fin", rid=1, failed=False)   # append lands on clean bytes
        j2.close()
        recs = RequestJournal.load(p)
        assert [r["k"] for r in recs] == ["admit", "fin"]

    def test_interior_blank_line_raises_not_silently_truncates(self, tmp_path):
        """A blank line BETWEEN committed records is damage (the writer
        never emits one): it must raise PT-SRV-004, not make the byte
        accounting undercount so the constructor's torn-tail truncate
        chops the newline off a committed record (welding the next append
        onto it — two records then vanish as a 'torn tail')."""
        p = str(tmp_path / "j.jrnl")
        j = RequestJournal(p)
        j.append("admit", rid=1, prompt=[1], max_new=1, eos=None, temp=0.0,
                 top_p=1.0, top_k=0, seed=1, deadline_s=None, priority=1)
        j.append("fin", rid=1, failed=False)
        j.close()
        first, second = open(p, "rb").read().split(b"\n")[:2]
        open(p, "wb").write(first + b"\n\n" + second + b"\n")
        with pytest.raises(JournalCorrupt, match="blank"):
            RequestJournal.load(p)
        # a stray trailing newline (nothing after it) is torn-tail
        # territory: tolerated and truncated away
        open(p, "wb").write(first + b"\n\n")
        j2 = RequestJournal(p)
        assert [r["k"] for r in j2.records] == ["admit"]
        j2.append("fin", rid=1, failed=False)
        j2.close()
        assert [r["k"] for r in RequestJournal.load(p)] == ["admit", "fin"]

    def test_midfile_corruption_raises_pt_srv_004(self, tmp_path):
        p = str(tmp_path / "j.jrnl")
        j = RequestJournal(p)
        j.append("admit", rid=1, prompt=[1], max_new=1, eos=None, temp=0.0,
                 top_p=1.0, top_k=0, seed=1, deadline_s=None, priority=1)
        j.append("fin", rid=1, failed=False)
        j.close()
        raw = bytearray(open(p, "rb").read())
        raw[12] ^= 0xFF                         # damage the FIRST record
        open(p, "wb").write(bytes(raw))
        with pytest.raises(JournalCorrupt, match="PT-SRV-004"):
            RequestJournal.load(p)


# ---------------------------------------------------------------------------
# step watchdog (host-only)
# ---------------------------------------------------------------------------

class TestStepWatchdog:
    def test_overrun_flagged_mid_hang_then_on_disarm(self):
        wd = StepWatchdog(0.05)
        try:
            with pytest.warns(RuntimeWarning, match="PT-SRV-002"):
                wd.arm("step:1")
                time.sleep(0.2)                 # the "hang"
                assert wd.fired                 # flagged WHILE still stuck
            assert wd.disarm() is True
            assert len(wd.overruns) == 1 and wd.overruns[0][0] == "step:1"
        finally:
            wd.close()

    def test_under_budget_clean_and_rearmable(self):
        wd = StepWatchdog(5.0)
        try:
            wd.arm("a")
            assert wd.disarm() is False
            wd.arm("b")                         # re-arm after a clean step
            assert wd.disarm() is False and not wd.overruns
        finally:
            wd.close()


# ---------------------------------------------------------------------------
# priority admission + shedding
# ---------------------------------------------------------------------------

def test_priority_orders_queue_fifo_within_class(model):
    cfg, m = model
    e = ContinuousBatchingEngine(m, max_batch=1, max_len=32, page_size=8)
    reqs = [Request(_prompt(cfg, 4, 200 + i), max_new_tokens=2, priority=pr)
            for i, pr in enumerate([Request.PRIORITY_LOW,
                                    Request.PRIORITY_HIGH,
                                    Request.PRIORITY_NORMAL,
                                    Request.PRIORITY_HIGH])]
    for r in reqs:
        e.add_request(r)
    # HIGH admits first (FIFO within the class), then NORMAL, then LOW
    assert [r.rid for r in e._queue] == [reqs[1].rid, reqs[3].rid,
                                         reqs[2].rid, reqs[0].rid]


def test_shed_infeasible_at_submit_survivors_byte_identical(model):
    cfg, m = model
    e = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                 block_size=2)
    warm = Request(_prompt(cfg, 4, 210), max_new_tokens=2)
    e.add_request(warm)
    e.run_until_done(max_steps=200)             # compiles + measures tok/s
    pa, pb = _prompt(cfg, 6, 211), _prompt(cfg, 6, 212)
    refs = [_ref(m, pa, 8), _ref(m, pb, 8)]
    ra = Request(pa, max_new_tokens=8, seed=3)
    rb = Request(pb, max_new_tokens=8, seed=4)
    e.add_request(ra)
    e.add_request(rb)
    e.step()                                    # survivors decoding
    doomed = Request(_prompt(cfg, 6, 213), max_new_tokens=16,
                     deadline_s=1e-3)
    with pytest.raises(RequestShed, match="PT-SRV-003"):
        e.add_request(doomed)
    # shed BEFORE touching engine state: no slot, no queue entry, no tokens
    assert doomed._n_out == 0
    assert doomed.rid not in [r.rid for r in e._queue]
    assert doomed.rid not in [r.rid for r in e._slots if r is not None]
    assert e.stats["shed"] == 1
    e.run_until_done(max_steps=300)
    assert [ra.tokens, rb.tokens] == refs       # survivors byte-identical
    # satellite: the retry-stats registry snapshot rides in engine.stats
    assert "retry_attempts" in e.stats and "retry_giveups" in e.stats


def test_resume_submit_never_shed(model, tmp_path):
    """Journaled work is never refused: ``submit(resume=True)`` (the fleet
    failover / drain-migration path) bypasses feasibility shedding and
    backpressure — both were charged at the ORIGINAL submit, and a busy
    survivor shedding a rescued request would strand it (its journal of
    record already handed it over)."""
    cfg, m = model

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2,
                                        max_queue=1)

    sup = ServingSupervisor(build, str(tmp_path / "j.jrnl"))
    warm = Request(_prompt(cfg, 4, 240), max_new_tokens=2)
    sup.submit(warm)
    sup.run_until_done(max_steps=200)           # arms the decode-rate EMA
    doomed_kw = dict(max_new_tokens=16, deadline_s=1e-3)
    with pytest.raises(RequestShed):            # a NORMAL submit sheds it
        sup.submit(Request(_prompt(cfg, 6, 241), **doomed_kw))
    rescued = Request(_prompt(cfg, 6, 242), **doomed_kw)
    sup.submit(rescued, resume=True)            # a rescued one must admit
    assert rescued.rid in sup._live
    assert sup.engine.shed_infeasible and sup.engine.max_queue == 1  # restored
    sup.run_until_done(max_steps=300)           # (it may still deadline out
    sup.close()                                 #  later — that's its own fate)


# ---------------------------------------------------------------------------
# supervisor: crash recovery, restart + backpressure, brownout
# ---------------------------------------------------------------------------

def _build_prefix(m, max_queue=None, pack_rows=None):
    return ContinuousBatchingEngine(
        m, max_batch=2, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8, pack_rows=pack_rows),
        max_queue=max_queue)


@pytest.mark.slow   # two full supervisor cycles of engine compiles; the
#                     crash path is also CI-gated end-to-end by the
#                     serving_crash fault drill, and fast in-process replay
#                     determinism rides in the journal-restart test below
def test_crash_recovery_bit_identical_greedy_and_seeded(model, tmp_path):
    """FaultPlan ``serving.step`` kill mid-decode: the supervisor rebuilds
    from the journal (fresh pool, empty radix) and the recovered streams —
    greedy AND seeded — are bit-identical to an uninterrupted run, with the
    already-delivered prefix never re-emitted past the high-water mark."""
    cfg, m = model
    pa, pb = _prompt(cfg, 8, 220), _prompt(cfg, 6, 221)

    def wave():
        return [Request(pa, max_new_tokens=6, seed=70),
                Request(pb, max_new_tokens=10, temperature=0.9, seed=71)]

    ref_eng = _build_prefix(m)                  # uninterrupted reference
    refs = wave()
    for r in refs:
        ref_eng.add_request(r)
    ref_eng.run_until_done(max_steps=300)

    plan = FaultPlan(seed=5, specs=[
        FaultSpec("serving.step", "kill", at=2, count=1)])
    sup = ServingSupervisor(lambda: _build_prefix(m),
                            str(tmp_path / "j.jrnl"))
    reqs = wave()
    with plan:
        for r in reqs:
            sup.submit(r)
        done = sup.run_until_done(max_steps=300)
    sup.close()
    assert plan.log, "serving.step kill never fired"
    assert sup.recoveries == 1 and sup.events[0][0] == "PT-SRV-001"
    assert set(done) == {r.rid for r in reqs}
    for got, want in zip(reqs, refs):
        assert got.done and not got.failed
        assert list(got.tokens) == list(want.tokens)
    # the journal tells the whole story: admits, a crash, a recovery
    kinds = [r["k"] for r in RequestJournal.load(str(tmp_path / "j.jrnl"))]
    assert "crash" in kinds and "recovered" in kinds
    assert kinds.count("fin") == 2


def test_journal_restart_replays_with_backpressure_in_flight(model, tmp_path):
    """Satellite: ``max_queue`` backpressure (EngineSaturated) exercised in
    prefix-cache mode with chunked prefills in flight, and the journal
    surviving a supervisor restart — the new supervisor over the same file
    replays every unfinished request bit-identically; the saturated-away
    request was never journaled and never resurrects."""
    cfg, m = model
    path = str(tmp_path / "j.jrnl")
    prompts = [_prompt(cfg, 24, 230), _prompt(cfg, 24, 231),
               _prompt(cfg, 6, 232), _prompt(cfg, 6, 233)]
    refs = {i: _ref(m, p, 4) for i, p in enumerate(prompts[:3])}

    # a pack of ONE 8-token row (pack_rows=1; _run_pack's budget is
    # max(mid-prefill slots, pack_rows)): the 24-token prompts are three
    # packs long, so prefills stay in flight across steps
    def build():
        return _build_prefix(m, max_queue=1, pack_rows=1)

    sup1 = ServingSupervisor(build, path)
    r0 = Request(prompts[0], max_new_tokens=4)
    sup1.submit(r0)
    sup1.step()                                 # slot 0: chunk 1 of 3
    assert sup1.engine._prefill_next == {0: 8}
    r1 = Request(prompts[1], max_new_tokens=4)
    sup1.submit(r1)
    sup1.step()                                 # one row a slot: 2/3, 1/3
    assert sup1.engine._prefill_next == {0: 16, 1: 8}   # prefills IN FLIGHT
    r2 = Request(prompts[2], max_new_tokens=4)
    sup1.submit(r2)                             # queued (high-water mark)
    with pytest.raises(EngineSaturated):
        sup1.submit(Request(prompts[3], max_new_tokens=4))
    rids = [r0.rid, r1.rid, r2.rid]
    sup1.step()
    sup1.close()                                # "process death" mid-flight

    sup2 = ServingSupervisor(build, path)
    assert sorted(sup2.requests) == sorted(rids)    # replay set == journal
    sup2.run_until_done(max_steps=500)
    sup2.close()
    for i, rid in enumerate(rids):
        req = sup2.requests[rid]
        assert req.done and not req.failed
        assert list(req.tokens) == refs[i]
    kinds = [r["k"] for r in RequestJournal.load(path)]
    assert "recovered" in kinds and kinds.count("admit") == 3


def test_replay_deadline_eviction_is_not_divergence(model, tmp_path):
    """A replay twin that dies an ORDINARY death mid-recovery (its deadline
    expires again during the rebuilt engine's catch-up) must surface as
    that failure — not as a PT-SRV-005 replay-divergence data-integrity
    alarm just because its output stops short of the delivered mark."""
    cfg, m = model
    sup = ServingSupervisor(lambda: _build_prefix(m),
                            str(tmp_path / "j.jrnl"))
    req = Request(_prompt(cfg, 8, 240), max_new_tokens=8, deadline_s=60.0)
    sup.submit(req)
    while req._n_out < 2:                       # deliver past the mark
        sup.step()
    # shrink the journaled deadline so the twin cannot survive the
    # rebuild's catch-up (deterministic stand-in for a deadline shorter
    # than the rebuild's compile time), then crash the engine
    sup._meta[req.rid]["deadline_s"] = 1e-3
    with FaultPlan(seed=9, specs=[       # at=0: first step under the plan
            FaultSpec("serving.step", "kill", at=0, count=1)]):
        done = sup.run_until_done(max_steps=300)
    sup.close()
    assert sup.recoveries == 1
    assert req.rid in done and req.failed
    assert "deadline" in (req.error or "")
    assert not any(c == "PT-SRV-005" for c, _ in sup.events), sup.events


def test_brownout_enters_serves_legacy_exits_hysteretically(model):
    """Sustained pool pressure: the engine flushes idle cached blocks,
    stops prefix-cache admission and serves the cache-off-identical path
    (PT-SRV-006); pressure clearing for ``exit_after`` steps with real
    headroom re-enables the cache."""
    cfg, m = model
    e = ContinuousBatchingEngine(
        m, max_batch=2, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8),
        brownout=BrownoutConfig(enter_after=2, exit_free_frac=0.5,
                                exit_after=2))
    pa = _prompt(cfg, 8, 240)                   # exactly one full page
    ra = Request(pa, max_new_tokens=8)
    e.add_request(ra)
    e.run_until_done(max_steps=200)             # registers pa's chain
    assert e._radix.match(pa), "prompt chain should be cached"
    e._alloc.hold(e._alloc.free_blocks)         # pool exhausted
    rb = Request(pa, max_new_tokens=9)          # needs 3 pages; 1 evictable
    e.add_request(rb)
    hits0 = e.stats["hit_tokens"]
    for _ in range(3):                          # deferrals accumulate
        e.step()
    assert e._brownout_active and e.stats["brownouts"] == 1
    assert not e._radix.match(pa)               # idle cache flushed to pool
    assert rb._n_out == 0                       # still deferred (held pool)
    e._alloc.release_held()
    e.run_until_done(max_steps=300)
    assert e.stats["hit_tokens"] == hits0       # admission skipped the cache
    assert list(rb.tokens) == _ref(m, pa, 9)    # byte-identical to cache-off
    for _ in range(4):                          # pressure-free, pool free
        e.step()
    assert not e._brownout_active               # hysteretic exit
    assert e.stats["brownout_steps"] > 0
    rc = Request(pa, max_new_tokens=8)          # cache re-enabled: register
    e.add_request(rc)
    e.run_until_done(max_steps=200)
    rd = Request(pa, max_new_tokens=8)
    e.add_request(rd)
    e.run_until_done(max_steps=200)
    assert e.stats["hit_tokens"] > hits0        # ...and match again
    assert list(rc.tokens) == list(rd.tokens) == _ref(m, pa, 8)


@pytest.mark.slow   # the fault drill (CI-gated) covers this end-to-end
def test_stall_watchdog_triggers_rebuild_streams_identical(model, tmp_path):
    """FaultPlan ``serving.stall``: the StepWatchdog flags PT-SRV-002 while
    the step hangs; the supervisor rebuilds from the journal and the
    post-rebuild streams are bit-identical."""
    cfg, m = model

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2)

    sup = ServingSupervisor(build, str(tmp_path / "j.jrnl"))
    prompts = [_prompt(cfg, 6, 250), _prompt(cfg, 6, 251)]

    def wave():
        reqs = [Request(p, max_new_tokens=8, seed=80 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            sup.submit(r)
        return reqs

    warm = wave()
    sup.run_until_done(max_steps=200)           # compile everything first
    refs = [list(r.tokens) for r in warm]
    sup.set_step_budget(0.6)
    plan = FaultPlan(seed=6, specs=[
        FaultSpec("serving.stall", "stall", at=2, count=1, arg=1.5)])
    reqs = wave()
    import warnings

    with plan, warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        sup.run_until_done(max_steps=200)
    sup.close()
    assert plan.log, "stall never fired"
    assert "PT-SRV-002" in [c for c, _ in sup.events]
    assert [list(r.tokens) for r in reqs] == refs
