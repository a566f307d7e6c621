"""The engine's step loop (inference/serving.py — docs/SERVING.md "The
mega-step"): device-resident block tables / positions / sampling state
updated by traced scatters, ONE jitted decode program over all rows with
masked inactive rows, prompt-packing prefill, and O(active) host
bookkeeping. One family at every ``max_batch`` since PR 30.

The contract under test: token streams are BYTE-IDENTICAL to
``generate()`` (greedy) and to recorded streams
(``tests/data/serving_legacy_wave_streams.json``: the greedy ones as the
legacy per-slot step programs produced them at PR 30's parent, the seeded
ones as a 2-slot engine of PR 39's tree did, whose sampler draws in id
order), at any slot count,
prefix cache on or off, warm or cold, across COW divergence and crash
replay. The 128-slot acceptance pin (ISSUE 10) is slow-marked; every
behavior has a fast 8-slot pin here.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          PrefixCacheConfig, Request)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def recorded():
    """What a 2-slot engine served for ``_wave`` and the eos request: the
    legacy family (``fused=False``) at PR 30's parent and, for the two seeded
    streams, PR 39's tree (the file says which). The sampled streams have no
    other reference (``generate()`` splits its key, the engine folds (seed,
    position))."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "serving_legacy_wave_streams.json")) as f:
        return json.load(f)


def _ref(m, prompt, n):
    out = m.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                     max_new_tokens=n, temperature=0.0).numpy()[0]
    return [int(t) for t in out]


def _want(m, prompts, kws, recorded):
    """The wave's reference streams: ``generate()`` for the greedy
    requests (which the recording must equal too), the recording for the
    seeded ones."""
    want = [list(s) for s in recorded["wave"]]
    for i, (p, kw) in enumerate(zip(prompts, kws)):
        if "temperature" not in kw:
            assert want[i] == _ref(m, p, kw["max_new_tokens"])
    return want


@pytest.fixture(scope="module")
def fus(model):
    """Engine with the prefix cache off (8 slots — same programs the
    128-slot engine runs, cheaper to compile)."""
    _, m = model
    return ContinuousBatchingEngine(m, max_batch=8, max_len=64, page_size=8,
                                    block_size=4)


@pytest.fixture(scope="module")
def fusp(model):
    """Engine with the prefix cache + packed prefill."""
    _, m = model
    return ContinuousBatchingEngine(
        m, max_batch=8, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


def _wave(cfg):
    """Mixed greedy/seeded requests; prompt 16 is a full-page multiple so
    a warm re-serve takes the FULL-prompt-hit COW path, and prompt 40 is
    LONGER than the fused engine's prefill_chunk (16) so the packed
    prefill carries several chunks of one prompt in a single call — the
    append-before-gather ordering `_run_pack` stakes bit-identity on."""
    prompts = [_prompt(cfg, n, 300 + n) for n in (5, 16, 9, 16, 40, 3)]
    kws = [dict(max_new_tokens=6), dict(max_new_tokens=4),
           dict(max_new_tokens=8, temperature=0.8, seed=7, top_k=5),
           dict(max_new_tokens=4, temperature=1.1, seed=3, top_p=0.9),
           dict(max_new_tokens=6), dict(max_new_tokens=8)]
    return prompts, kws


def _serve(eng, prompts, kws, stagger=True):
    reqs = [Request(p, **k) for p, k in zip(prompts, kws)]
    head, tail = (reqs[:3], reqs[3:]) if stagger else (reqs, [])
    for r in head:
        eng.add_request(r)
    if tail:
        eng.step()
        eng.step()
        for r in tail:
            eng.add_request(r)
    eng.run_until_done(max_steps=500)
    return [list(r.tokens) for r in reqs]


def test_fused_matches_legacy_greedy_and_seeded(model, recorded, fus):
    """The core contract: 8-slot streams == generate() and the recorded
    legacy 2-slot streams, byte for byte, mixed greedy + seeded sampling,
    staggered arrivals."""
    cfg, m = model
    prompts, kws = _wave(cfg)
    want = _want(m, prompts, kws, recorded)
    got = _serve(fus, prompts, kws)
    assert got == want
    assert fus.stats["fused_updates"] > 0      # scatters actually ran
    # device state drained: every row inactive, every slot free again
    assert not np.asarray(fus._dev_act).any()
    assert fus.active_slots() == 0 and len(fus._free_slots) == fus.max_batch


def test_fused_prefix_warm_cold_cow_identity(model, recorded, fusp):
    """Prefix cache: cold == warm == reference. The warm wave re-serves
    two full-page prompts, so the batched-COW path (one device dispatch
    for the wave's copies) and the radix hits are both on the tested
    path; the packed prefill must also have fired."""
    cfg, m = model
    prompts, kws = _wave(cfg)
    want = _want(m, prompts, kws, recorded)
    cold = _serve(fusp, prompts, kws)
    warm = _serve(fusp, prompts, kws)
    assert cold == want and warm == want
    assert fusp.stats["hit_tokens"] > 0
    assert fusp.stats["cow_copies"] > 0        # full-prompt hits -> COW
    assert fusp.stats["packed_rows"] > 0       # prompt-packing prefill ran
    # every table row parked on device once drained
    assert (np.asarray(fusp.caches["tables"]) == fusp._park).all()


def test_fused_eos_early_exit(model, recorded, fus):
    """eos-carrying batches pace at block_size and stop early, token for
    token as generate() and the recorded legacy stream do — with an eos id
    the stream never meets (the recorded case) and with one it meets
    mid-block (the cut)."""
    cfg, m = model
    p = _prompt(cfg, 7, 401)
    ref = _ref(m, p, 12)
    assert recorded["eos"] == ref and 3 not in ref
    cut = ref.index(ref[5]) + 1            # mid-block at block_size 4
    for eos, want in ((3, ref), (ref[5], ref[:cut])):
        r = Request(p, max_new_tokens=12, eos_token_id=eos)
        fus.add_request(r)
        fus.run_until_done(max_steps=200)
        assert list(r.tokens) == want


def test_heads_of_128_serve_generates_streams_through_the_row_append():
    """Heads the paged kernel takes (``d % 128 == 0``) append through the
    row form of ``append_paged_kv``'s scatter; every other engine test
    here has heads of 16 and takes the other form. Greedy streams equal
    ``generate()``'s cold, and warm through prefix hits and COW."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops.paged_attention import _kernel_takes

    paddle.seed(12)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=256,
                           num_attention_heads=2, num_key_value_heads=1)
    m = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        m, max_batch=4, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))
    assert _kernel_takes(eng.caches["kv"][0][0])
    shared = _prompt(cfg, 16, 41)
    prompts = [np.concatenate([shared, _prompt(cfg, n, 50 + n)])
               for n in (0, 3, 9, 20)] + [_prompt(cfg, 5, 60)]
    kws = [dict(max_new_tokens=6)] * len(prompts)
    want = [_ref(m, p, 6) for p in prompts]
    assert _serve(eng, prompts, kws) == want            # cold
    hits = eng.stats["hit_tokens"]
    assert _serve(eng, prompts, kws) == want            # warm
    assert eng.stats["hit_tokens"] > hits and eng.stats["cow_copies"] > 0


def test_fused_false_is_refused(model):
    """``fused=`` chooses nothing since PR 30: None and True are accepted
    (chipbench's cell files hand ``"fused": true`` over), False is refused
    by name before anything is built."""
    _, m = model
    with pytest.raises(ValueError, match="fused=False.*PR 30"):
        ContinuousBatchingEngine(m, max_batch=2, max_len=64, page_size=8,
                                 fused=False)
    ContinuousBatchingEngine(m, max_batch=2, max_len=64, page_size=8,
                             fused=True)


def test_eight_slots_take_the_mega_step_and_upload_no_table(model,
                                                            monkeypatch):
    """An engine built with max_batch=8 and no flag (the size at which the
    parent chose the legacy family) dispatches ``jit_pt_decode_block``
    through ``_build_mega_jit``, and between decode blocks nothing is
    uploaded: the device table object changes only where a slot update
    was queued (an admission or a release)."""
    cfg, m = model
    eng = ContinuousBatchingEngine(
        m, max_batch=8, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))
    built = []
    real = eng._build_mega_jit
    monkeypatch.setattr(eng, "_build_mega_jit",
                        lambda: built.append(real()) or built[-1])
    for i in range(2):
        # an eos id no token equals: blocks of block_size, values read back
        eng.add_request(Request(_prompt(cfg, 5, 700 + i), max_new_tokens=17,
                                eos_token_id=-1))
    eng.step()                              # admit, prefill, first tokens
    seen = []
    while eng.has_work():
        queued = bool(eng._upd)
        before = eng.caches["tables"]
        eng.step()
        seen.append((queued, eng.caches["tables"] is before))
    assert len(built) == 1 and eng._jit_mega is built[0]
    assert built[0].__wrapped__.__name__ == "pt_decode_block"
    assert ("pt_decode_block", (4, False)) in eng._built
    # steps with nothing queued kept the very same device array
    quiet = [same for queued, same in seen if not queued]
    assert len(quiet) >= 2 and all(quiet)
    assert not hasattr(eng, "_tables_host")


def test_fused_deadline_eviction_survivor_unharmed(model, fusp):
    """Deadline eviction: the expired slot is failed and its
    row parked via the update queue; the surviving stream is untouched.
    The no-deadline fast path stays O(1) (``_n_deadlined`` gate)."""
    cfg, _ = model
    import time

    pa, pb = _prompt(cfg, 5, 402), _prompt(cfg, 9, 403)
    ref = Request(pa, max_new_tokens=6)
    fusp.add_request(ref)
    fusp.run_until_done(max_steps=200)
    surv = Request(pa, max_new_tokens=6)
    doomed = Request(pb, max_new_tokens=40, deadline_s=0.0005)
    fusp.add_request(surv)
    fusp.shed_infeasible = False    # exercise EVICTION, not submit shedding
    try:
        fusp.add_request(doomed)
    finally:
        fusp.shed_infeasible = True
    assert fusp._n_deadlined == 1
    fusp.step()
    time.sleep(0.01)
    fusp.run_until_done(max_steps=200)
    assert doomed.failed and "deadline" in doomed.error
    assert fusp._n_deadlined == 0
    assert not surv.failed and list(surv.tokens) == list(ref.tokens)


def test_fused_counters_track_occupancy(model, fus):
    """O(active) bookkeeping invariants: occupied dict + free-slot deque +
    has_work stay consistent with the slot array through admit/finish."""
    cfg, _ = model
    reqs = [Request(_prompt(cfg, 5, 500 + i), max_new_tokens=16)
            for i in range(3)]
    for r in reqs:
        fus.add_request(r)
    assert fus.has_work()
    fus.step()
    assert fus.active_slots() == 3
    assert len(fus._free_slots) == fus.max_batch - 3
    assert sorted(fus._occupied) == [i for i, s in enumerate(fus._slots)
                                     if s is not None]
    fus.run_until_done(max_steps=200)
    assert not fus.has_work() and fus.active_slots() == 0
    assert len(fus._free_slots) == fus.max_batch
    assert all(r.done and not r.failed for r in reqs)


@pytest.mark.slow   # crash + rebuild = a second fused compile wave (~23s);
#                     replay-determinism keeps fast coverage via
#                     test_serving_recovery's journal-restart test (same
#                     posture as PR 5's crash-recovery slow-mark)
def test_fused_crash_replay_bit_identical(model, tmp_path):
    """ServingSupervisor over the engine: a ``serving.step`` kill
    mid-wave rebuilds from the journal and the replayed streams (greedy +
    seeded) are byte-identical to an uninterrupted run — the
    device-resident state is fully reconstructible from the journal, as
    the recovery contract requires."""
    cfg, m = model
    from paddle_tpu.inference.recovery import ServingSupervisor

    def build():
        return ContinuousBatchingEngine(
            m, max_batch=4, max_len=32, page_size=8, block_size=2,
            prefix_cache=PrefixCacheConfig(prefill_chunk=8))

    pa, pb = _prompt(cfg, 8, 601), _prompt(cfg, 6, 602)

    def wave():
        return [Request(pa, max_new_tokens=6, seed=70),
                Request(pb, max_new_tokens=10, temperature=0.9, seed=71)]

    ref_eng = build()
    refs = wave()
    for r in refs:
        ref_eng.add_request(r)
    ref_eng.run_until_done(max_steps=300)

    plan = FaultPlan(seed=5, specs=[
        FaultSpec("serving.step", "kill", at=2, count=1)])
    sup = ServingSupervisor(build, str(tmp_path / "fused.jrnl"))
    reqs = wave()
    with plan:
        for r in reqs:
            sup.submit(r)
        done = sup.run_until_done(max_steps=300)
    sup.close()
    assert plan.log, "serving.step kill never fired"
    assert sup.recoveries == 1
    assert set(done) == {r.rid for r in reqs}
    for got, want in zip(reqs, refs):
        assert got.done and not got.failed
        assert list(got.tokens) == list(want.tokens)


@pytest.mark.parametrize("values_on_host", [True, False],
                         ids=["values_on_host", "values_pending"])
def test_tracer_batched_stamps_equal_per_slot_stamps(values_on_host):
    """first_tokens / tokens_batch (one lock per step) must book exactly
    what the per-slot calls book: every row's token progress beside the
    engine-lane ``pt.serve.decode.dispatch`` program span — right after the
    dispatch when the block's values are on the host, and later
    (``tokens_batch`` from ``_drain_pending``) when they are not."""
    from paddle_tpu.observability.tracing import TraceRecorder, program_span

    a, b = TraceRecorder(), TraceRecorder()
    for rid in (1, 2):
        a.submit(rid, 4, 8)
        b.submit(rid, 4, 8)
    # per-slot stamping
    for rid in (1, 2):
        a.first_token(rid)
        a.tokens(rid, 1)
    a.span("pt.serve.decode.dispatch", None, a.now(), n_steps=4, rows=2,
           parent=None)
    for rid in (1, 2):
        a.tokens(rid, 5)
    # batched stamping (the engine's)
    b.first_tokens([(1, 1), (2, 1)])
    with program_span("serve.decode.dispatch", b, n_steps=4, rows=2):
        pass
    if not values_on_host:
        assert b.slo_summary()["tokens_streamed"] == 2   # not yet on host
    b.tokens_batch([(1, 5), (2, 5)])
    sa, sb = a.slo_summary(), b.slo_summary()
    assert sa["tokens_streamed"] == sb["tokens_streamed"] == 10
    assert sa["submitted"] == sb["submitted"] == 2
    assert ([e["name"] for e in a.events if e["tid"] == 1]
            == [e["name"] for e in b.events if e["tid"] == 1])
    span_a, = [e for e in a.events if e["name"] == "pt.serve.decode.dispatch"]
    span_b, = [e for e in b.events if e["name"] == "pt.serve.decode.dispatch"]
    assert span_a["args"] == span_b["args"] == {"n_steps": 4, "rows": 2}
    assert span_a["tid"] == span_b["tid"] == 0      # the engine lane


@pytest.mark.slow   # one 128-row compile wave (~3-4 min budget class) —
#                     the fast 8-slot pins above cover every behavior;
#                     this is the ISSUE 10 acceptance config end-to-end
def test_fused_128_slots_byte_identical_to_legacy(model):
    """Acceptance pin: a max_batch=128 engine (prefix cache + packed
    prefill + batched COW) serves a 160-request greedy wave with every
    stream byte-identical to generate() (the reference the legacy 8-slot
    engine was held to), cold AND warm, and the engine drains clean."""
    cfg, m = model
    eng = ContinuousBatchingEngine(
        m, max_batch=128, max_len=32, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8, extra_blocks=16))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (8 + (i % 3) * 4,)).astype(np.int32)
               for i in range(160)]
    news = [4 + (i % 4) * 2 for i in range(160)]

    def wave(e):
        reqs = [Request(p, max_new_tokens=k)
                for p, k in zip(prompts, news)]
        for r in reqs:
            e.add_request(r)
        e.run_until_done(max_steps=2000)
        return [list(r.tokens) for r in reqs]

    cold = wave(eng)
    warm = wave(eng)
    # greedy streams are prefixes of one another: one generate() call a
    # prompt length, cut to each request's budget
    want = [None] * 160
    for n in (8, 12, 16):
        rows = [i for i, p in enumerate(prompts) if len(p) == n]
        toks = m.generate(paddle.to_tensor(np.stack([prompts[i]
                                                     for i in rows])),
                          max_new_tokens=max(news),
                          temperature=0.0).numpy()
        for i, row in zip(rows, toks):
            want[i] = [int(t) for t in row[:news[i]]]
    assert cold == want and warm == want
    assert eng.stats["cow_copies"] > 0 and eng.stats["packed_rows"] > 0
    assert eng.active_slots() == 0 and len(eng._free_slots) == 128
