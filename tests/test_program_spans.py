"""Program spans and device names (docs/OBSERVABILITY.md): the ``pt.*``
host spans inside ``step()``, the always-on counters beside them, the names
the device sees (module names, kernel names, name scopes), and the tracer
stamps at materialisation on the path without eos."""

import glob
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          PrefixCacheConfig, Request, SpecConfig)
from paddle_tpu.observability.tracing import TraceRecorder, program_span

EOS = 1 << 20       # an eos id no token reaches: every block is read back


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


def _engine(m, tracer=None, **kw):
    kw.setdefault("prefix_cache",
                  PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))
    return ContinuousBatchingEngine(m, max_batch=4, max_len=64, page_size=8,
                                    block_size=4, tracer=tracer,
                                    **kw)


def _requests(cfg, eos, n=5):
    rng = np.random.default_rng(5)
    return [Request(rng.integers(3, cfg.vocab_size, 6 + 5 * i).astype(np.int32),
                    max_new_tokens=5 + i, eos_token_id=eos, seed=i + 1,
                    **(dict(temperature=0.8, top_p=0.9) if i % 2 else {}))
            for i in range(n)]


def _run(eng, reqs):
    for r in reqs:
        eng.add_request(r)
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
    eng.finished()
    return calls


@pytest.fixture(scope="module")
def served(model):
    """A fused prefix-cache engine, warmed by one wave, then a second wave
    under a fresh recorder: (engine, recorder, calls, stats before/after)."""
    cfg, m = model
    eng = _engine(m)
    for _ in range(2):                      # cold, then prefix-warm: every
        _run(eng, _requests(cfg, EOS))      # program of the third wave built
    rec = TraceRecorder()
    eng.tracer = rec
    before = dict(eng.stats)
    calls = _run(eng, _requests(cfg, EOS))
    return eng, rec, calls, before, dict(eng.stats)


def _pt(rec):
    return [e for e in rec.events if e["name"].startswith("pt.")]


def test_step_spans_nest_and_name_their_parent(served):
    _, rec, calls, _, _ = served
    spans = _pt(rec)
    steps = [e for e in spans if e["name"] == "pt.serve.step"]
    assert len(steps) == calls
    assert all("parent" not in e["args"] for e in steps)
    assert [e["args"]["step"] for e in steps] == sorted(
        e["args"]["step"] for e in steps)
    names = {e["name"] for e in spans}
    assert {"pt.serve.admit", "pt.serve.prefill",
            "pt.serve.decode.dispatch", "pt.serve.wait",
            "pt.serve.emit"} <= names
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    # every other span lies inside the span it names as its parent
    for e in spans:
        if e["name"] == "pt.serve.step":
            continue
        if e["name"] == "pt.serve.call" and "parent" not in e["args"]:
            # finished() lands the queued releases outside any step
            assert e["args"]["program"] == "pt_slot_update"
            continue
        parent = e["args"]["parent"]
        assert parent.startswith("pt.serve."), e
        assert any(p["ts"] <= e["ts"] + 1e-3
                   and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
                   for p in by_name[parent]), e
    # what a span is for rides its args
    d = by_name["pt.serve.decode.dispatch"][-1]["args"]
    assert {"n_steps", "rows", "do_sample"} <= set(d)
    assert {"admitted", "deferred"} <= set(by_name["pt.serve.admit"][0]["args"])
    assert {"tokens", "finished"} <= set(by_name["pt.serve.emit"][0]["args"])
    assert by_name["pt.serve.wait"][0]["args"]["what"] in (
        "decode_block", "first_token", "pending")


def test_children_cover_the_step(served):
    """Under 5% of the steps' time lies outside a child span."""
    _, rec, _, _, _ = served
    spans = _pt(rec)
    total = sum(e["dur"] for e in spans if e["name"] == "pt.serve.step")
    covered = sum(e["dur"] for e in spans
                  if e["args"].get("parent") == "pt.serve.step")
    assert total > 0
    assert covered <= total * (1 + 1e-6)
    assert (total - covered) / total < 0.05


def test_counters_count_what_ran(served):
    _, rec, calls, s0, s1 = served
    assert s1["steps"] - s0["steps"] == calls
    spans = _pt(rec)
    blocks = [e for e in spans if e["name"] == "pt.serve.decode.dispatch"
              and "n_steps" in e["args"]]
    assert s1["decode_blocks"] - s0["decode_blocks"] == len(blocks)
    assert (s1["decode_block_steps"] - s0["decode_block_steps"]
            == sum(e["args"]["n_steps"] for e in blocks))
    wall = s1["step_wall_s"] - s0["step_wall_s"]
    wait = s1["device_wait_s"] - s0["device_wait_s"]
    assert 0.0 < wait <= wall
    # the waits are the pt.serve.wait spans (recorder and counter agree to
    # within the clock reads around them)
    in_spans = sum(e["dur"] for e in spans if e["name"] == "pt.serve.wait")
    assert in_spans * 1e-6 == pytest.approx(wait, rel=0.2, abs=2e-3)
    assert 0.0 < s1["step_max_s"] and \
        0.0 <= s1["step_max_wait_s"] <= s1["step_max_s"]
    # warmed: the second wave built nothing
    assert s1["programs_built"] == s0["programs_built"] > 0
    assert not [e for e in spans if e["name"] == "pt.serve.build"]


def test_first_calls_run_under_build_spans(model):
    cfg, m = model
    rec = TraceRecorder()
    eng = _engine(m, tracer=rec)
    _run(eng, _requests(cfg, EOS, n=3))
    builds = [e for e in _pt(rec) if e["name"] == "pt.serve.build"]
    assert len(builds) == eng.stats["programs_built"] > 0
    programs = {e["args"]["program"] for e in builds}
    assert {"pt_decode_block", "pt_prefill_chunk", "pt_first_token",
            "pt_slot_update"} <= programs
    # each decode length and sampling mode is a program of its own, which
    # compile_cache_entries (one entry for the mega-step) does not count
    decode = {e["args"]["key"] for e in builds
              if e["args"]["program"] == "pt_decode_block"}
    assert len(decode) > 1
    assert eng.stats["programs_built"] > eng.stats["compile_cache_entries"]
    assert all(e["args"]["parent"] in ("pt.serve.decode.dispatch",
                                       "pt.serve.prefill", "pt.serve.admit")
               for e in builds)


def test_first_call_runs_above_a_frame_chunk_of_its_own():
    """``first_call`` hands its arguments through and asks CPython for a
    frame larger than any 16 KiB chunk of the frame stack, so that the
    frames of jax's tracing and lowering above it share one chunk (PERF.md
    section 6, PR 24: the warm set-up moved by 30% with the alignment)."""
    import sys

    from paddle_tpu.framework.compile_cache import first_call

    seen = {}

    def fn(a, b=0, *, c=0):
        seen["caller"] = sys._getframe(1).f_code.co_name
        return a, b, c

    assert first_call(fn, 1, 2, c=3) == (1, 2, 3)
    assert seen["caller"] == "first_call"
    assert first_call.__code__.co_stacksize * 8 > 16 * 1024 * 32
    with pytest.raises(ZeroDivisionError):
        first_call(lambda: 1 / 0)


@pytest.mark.parametrize("prefix", [True, False],
                         ids=["prefix_cache", "static_pool"])
def test_stamps_wait_for_the_values_without_eos(model, prefix):
    """On the path without eos nothing is read at dispatch: first_token,
    token progress and the terminal are stamped when ``_drain_pending``
    brings the values to the host, in lifecycle order — whether the first
    tokens were booked by ``_emit_first`` (prefix cache) or by
    ``_emit_group`` (the pool layout without one)."""
    cfg, m = model
    rec = TraceRecorder()
    eng = (_engine(m, tracer=rec) if prefix else ContinuousBatchingEngine(
        m, max_batch=2, max_len=64, page_size=8, block_size=4, tracer=rec))
    reqs = _requests(cfg, None, n=2)
    for r in reqs:
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    assert all(r.done for r in reqs)
    # done by the schedule, values still on the device: nothing stamped
    assert eng._pending
    assert rec.slo_summary()["tokens_streamed"] == 0
    assert rec.slo_summary()["p50_time_to_first_token_ms"] is None
    assert len(rec.incomplete()) == 2
    eng.finished()                                     # drains
    assert rec.slo_summary()["tokens_streamed"] == sum(
        len(r.output) for r in reqs)
    assert rec.slo_summary()["p50_time_to_first_token_ms"] is not None
    assert rec.incomplete() == []
    for r in reqs:
        chain = rec.lifecycle(r.rid)
        assert chain[0] == "submit" and chain[-1] == "finish"
        assert chain.index("first_token") < chain.index("finish")
        assert chain.count("finish") == 1


def test_spec_block_spans_and_stamps(model):
    cfg, m = model
    rec = TraceRecorder()
    eng = _engine(m, tracer=rec, speculative=SpecConfig(k=2))
    reqs = [Request(np.tile(np.arange(3, 9, dtype=np.int32), 3),
                    max_new_tokens=8, seed=i + 1) for i in range(2)]
    _run(eng, reqs)
    assert eng.stats["spec_steps"] > 0
    builds = {e["args"]["program"] for e in _pt(rec)
              if e["name"] == "pt.serve.build"}
    assert "pt_spec_block" in builds
    waits = {e["args"]["what"] for e in _pt(rec)
             if e["name"] == "pt.serve.wait"}
    assert "spec_emit" in waits
    assert rec.slo_summary()["tokens_streamed"] == sum(
        len(r.output) for r in reqs)
    assert rec.incomplete() == []


def test_program_span_is_an_inactive_traceme_without_a_session():
    """No recorder, no profiler session: nothing is kept, the stack of
    open spans unwinds, and ``elapsed_s`` still reads."""
    from paddle_tpu.observability import tracing

    with program_span("serve.step", step=1) as outer:
        with program_span("serve.wait", what="x") as inner:
            assert tracing._open.stack == ["pt.serve.step", "pt.serve.wait"]
        inner.set(more=1)
    assert tracing._open.stack == []
    assert outer.elapsed_s >= inner.elapsed_s >= 0.0
    with pytest.raises(RuntimeError):
        with program_span("serve.step"):
            raise RuntimeError("boom")
    assert tracing._open.stack == []


# ---- the in-flight ledger (docs/OBSERVABILITY.md "What the device waits
# for"): starved time by host phase, one leaf span a program call ----------

_NEW = ("device_starved_s", "starved_emit_s", "starved_admit_s",
        "starved_prefill_s", "starved_dispatch_s", "starved_caller_s",
        "drains", "caller_over_1s", "caller_over_1s_s", "steps_over_1s",
        "steps_over_1s_wall_s", "steps_over_1s_wait_s",
        "steps_over_1s_starved_s")


def _phase_sum(st):
    return (st["starved_emit_s"] + st["starved_admit_s"]
            + st["starved_prefill_s"] + st["starved_dispatch_s"]
            + st["starved_caller_s"])


def test_phase_counters_sum_to_the_starved_time_in_every_step(model):
    cfg, m = model
    eng = _engine(m)
    for r in _requests(cfg, EOS):
        eng.add_request(r)
    while eng.has_work():
        eng.step()
        assert _phase_sum(eng.stats) == eng.stats["device_starved_s"]
        eng.finished()              # as the benchmark's driver polls
        assert _phase_sum(eng.stats) == eng.stats["device_starved_s"]
    st = eng.stats
    assert st["device_starved_s"] > 0.0 and st["drains"] > 0
    assert all(st[k] >= 0.0 for k in _NEW)
    # every phase the engine works in with the device empty got its part
    assert all(st[k] > 0.0 for k in ("starved_emit_s", "starved_prefill_s",
                                     "starved_dispatch_s", "starved_caller_s"))
    # floor and ceiling fit in the time there was
    assert st["device_starved_s"] + st["device_maybe_starved_s"] \
        < st["step_wall_s"] + st["starved_caller_s"] + 1.0


def test_drains_follow_the_reads_of_the_newest_call(model, monkeypatch):
    """A drain is stamped when the read of the NEWEST call returns (the
    block's, the first tokens') and never by ``_drain_pending``'s read of
    an older value."""
    from paddle_tpu.inference import serving

    cfg, m = model
    rec = TraceRecorder()
    eng = _engine(m, tracer=rec)
    reads = []
    read = serving._InFlight.read

    def spy(self, seq, now):
        before = self.stats["drains"]
        read(self, seq, now)
        reads.append((seq, self.called, self.stats["drains"] - before))

    monkeypatch.setattr(serving._InFlight, "read", spy)
    rng = np.random.default_rng(2)
    # without an eos id first: its block's values stay on the device ...
    eng.add_request(Request(rng.integers(3, cfg.vocab_size, 9).astype(
        np.int32), max_new_tokens=24, seed=1))
    eng.step()
    eng.step()
    assert eng._pending and not reads and eng.stats["drains"] == 0
    # ... then one with: the next block is read back, the older values first
    eng.add_request(Request(rng.integers(3, cfg.vocab_size, 9).astype(
        np.int32), max_new_tokens=6, eos_token_id=EOS, seed=2))
    while eng.has_work():
        eng.step()
    waits = [e["args"] for e in _pt(rec) if e["name"] == "pt.serve.wait"]
    assert [w["seq"] for w in waits] == [seq for seq, _, _ in reads]
    older = [(seq, called, d) for seq, called, d in reads if seq < called]
    assert older and all(d == 0 for _, _, d in older)
    assert {w["what"] for w in waits if w["seq"] in {s for s, _, _ in older}
            } == {"pending"}
    newest = [(seq, d) for seq, called, d in reads if seq == called]
    assert newest and all(d == 1 for _, d in newest)
    kinds = {w["what"] for w in waits
             if w["seq"] in {seq for seq, _ in newest}}
    assert {"decode_block", "first_token"} <= kinds
    assert eng.stats["drains"] == len(newest)


@pytest.mark.parametrize("spec", [False, True], ids=["scan", "speculative"])
def test_without_eos_nothing_is_read_and_the_ledger_stands_still(model, spec):
    cfg, m = model
    eng = _engine(m, **(dict(speculative=SpecConfig(k=2)) if spec else {}))
    reqs = [Request(np.tile(np.arange(3, 9, dtype=np.int32), 3),
                    max_new_tokens=8, seed=i + 1) for i in range(2)]
    for r in reqs:
        eng.add_request(r)
    while eng.has_work():
        eng.step()
    if spec:
        # the emit vector of a speculative dispatch IS read back, eos or not
        assert eng.stats["spec_steps"] > 0 and eng.stats["drains"] > 0
    else:
        assert eng._flight.called > 0 and eng._flight.done == 0
        assert all(eng.stats[k] == 0 for k in _NEW), eng.stats
    eng.finished()
    assert all(r.done and len(r.output) == 8 for r in reqs)
    assert _phase_sum(eng.stats) == eng.stats["device_starved_s"]


def test_a_known_gap_between_drain_and_call_goes_to_each_phase():
    """On a hand-made clock: the device drains at 10 ms, the next program
    is called at 15 ms, and the 5 ms between lie 1 ms in the step's own
    time, 1.5 in emit, 0.5 in admit and 2 in prefill."""
    from paddle_tpu.inference.serving import _InFlight

    st = {}
    fl = _InFlight(st)
    ms = 1e-3
    fl.step_begins(0.0)
    fl.enter("starved_dispatch_s", 0.5 * ms)
    fl.call(1 * ms)                              # seq 1: the decode block
    fl.enter("starved_dispatch_s", 2 * ms)       # the span's exit
    assert st["device_starved_s"] == 0.0 and st["drains"] == 0
    fl.read(1, 10 * ms)                          # its read returns
    assert st["drains"] == 1
    left = fl.enter("starved_emit_s", 11 * ms)
    fl.enter(left, 12.5 * ms)
    left = fl.enter("starved_admit_s", 12.5 * ms)
    fl.enter(left, 13 * ms)
    left = fl.enter("starved_prefill_s", 13 * ms)
    fl.call(15 * ms)                             # seq 2: the chunk
    fl.enter(left, 16 * ms)
    assert st["starved_dispatch_s"] == pytest.approx(1 * ms)
    assert st["starved_emit_s"] == pytest.approx(1.5 * ms)
    assert st["starved_admit_s"] == pytest.approx(0.5 * ms)
    assert st["starved_prefill_s"] == pytest.approx(2 * ms)
    assert st["starved_caller_s"] == 0.0
    assert st["device_starved_s"] == pytest.approx(5 * ms)
    assert _phase_sum(st) == st["device_starved_s"]
    # a read of the older call while a newer one flies stamps nothing
    fl.call(17 * ms)                             # seq 3
    fl.read(2, 18 * ms)
    assert st["drains"] == 1 and fl.done == 2
    fl.read(3, 19 * ms)
    fl.read(3, 19.5 * ms)                        # a second value of it
    assert st["drains"] == 2
    # the caller's poll with the device empty is the caller's ...
    fl.step_ends(20 * ms, has_work=True)
    fl.step_begins(24 * ms)
    assert st["starved_caller_s"] == pytest.approx(4 * ms)
    assert st["starved_dispatch_s"] == pytest.approx(2 * ms)   # 19 -> 20
    # ... unless it took over a second: then it is nobody's starved time
    fl.step_ends(25 * ms, has_work=True)
    fl.step_begins(25 * ms + 3.0)
    assert st["caller_over_1s"] == 1
    assert st["caller_over_1s_s"] == pytest.approx(3.0)
    assert st["starved_caller_s"] == pytest.approx(4 * ms)
    fl.call(25 * ms + 3.0 + 2 * ms)
    assert st["device_starved_s"] == pytest.approx(13 * ms)
    assert _phase_sum(st) == st["device_starved_s"]
    # and an engine left without work is not starved while it has none
    fl.read(4, 3.1)
    fl.step_ends(3.2, has_work=False)
    fl.step_begins(9.0)
    fl.call(9.001)
    assert st["caller_over_1s"] == 1
    assert st["device_starved_s"] == pytest.approx(13 * ms + 0.1)
    assert st["device_maybe_starved_s"] == 0.0


def test_a_finished_predecessor_without_a_drain_is_the_blind_spot():
    """A call whose predecessor's output is ready, with no drain stamped
    since, adds the time since that call to ``device_maybe_starved_s``."""
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import _InFlight

    st = {}
    fl = _InFlight(st)
    fl.step_begins(0.0)
    fl.call(0.001)
    fl.out = [(jnp.zeros(2).block_until_ready(), None)]   # as a kv list
    fl.call(0.003)
    assert st["device_maybe_starved_s"] == pytest.approx(0.002)
    assert st["device_starved_s"] == 0.0 and st["drains"] == 0
    fl.call(0.004)                      # no output noted: nothing is known
    assert st["device_maybe_starved_s"] == pytest.approx(0.002)
    # a caller away for over a second, finished() calling inside that
    # interval: only the time outside it counts
    fl.out = ready = jnp.zeros(2).block_until_ready()
    fl.step_ends(0.005, has_work=True)
    fl.call(0.006)                      # finished()'s releases
    assert st["device_maybe_starved_s"] == pytest.approx(0.004)     # 4 -> 6
    fl.out = ready
    fl.step_begins(2.005)
    fl.call(2.006)
    assert st["caller_over_1s_s"] == pytest.approx(2.0)
    assert st["device_maybe_starved_s"] == pytest.approx(0.005)     # 2.005 ->


def test_call_spans_are_leaves_under_the_phase_that_called(served):
    eng, rec, _, s0, s1 = served
    spans = _pt(rec)
    calls = [e for e in spans if e["name"] == "pt.serve.call"]
    assert calls
    parents = {}
    for e in calls:
        a = e["args"]
        assert {"program", "key", "seq", "drained"} <= set(a)
        assert a["drained"] in (0, 1)
        parents.setdefault(a["program"], set()).add(a.get("parent"))
    assert parents["pt_decode_block"] == {"pt.serve.decode.dispatch"}
    assert parents["pt_prefill_chunk"] == parents["pt_first_token"] \
        == {"pt.serve.prefill"}
    assert parents["pt_cow_copy"] == {"pt.serve.admit"}
    assert parents["pt_slot_update"] <= {"pt.serve.decode.dispatch", None}
    # numbered one by one, in order; no span lies inside a call
    seqs = [e["args"]["seq"] for e in calls]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert seqs[-1] == eng._flight.called
    assert not [e for e in spans if e["args"].get("parent") == "pt.serve.call"]
    # a wait names the call whose value it read, a step what it was starved
    by_seq = {e["args"]["seq"]: e["args"]["program"] for e in calls}
    for w in (e["args"] for e in spans if e["name"] == "pt.serve.wait"):
        assert by_seq[w["seq"]] == {"decode_block": "pt_decode_block",
                                    "first_token": "pt_first_token"}[w["what"]]
    steps = [e["args"] for e in spans if e["name"] == "pt.serve.step"]
    assert all({"starved_us", "maybe_starved_us", "wait_us"} <= set(a)
               for a in steps)
    in_steps = sum(a["starved_us"] for a in steps) * 1e-6
    whole = s1["device_starved_s"] - s0["device_starved_s"]
    caller = s1["starved_caller_s"] - s0["starved_caller_s"]
    assert in_steps == pytest.approx(whole - caller, rel=0.05, abs=1e-4)
    assert sum(a["wait_us"] for a in steps) * 1e-6 == pytest.approx(
        s1["device_wait_s"] - s0["device_wait_s"], rel=0.05, abs=1e-4)


def test_steps_over_the_cut_are_counted_with_what_held_them(model, monkeypatch):
    """``steps_over_1s``: a step that built no program and took longer than
    the cut (lowered here; the ``serving.stall`` fault site sleeps the
    step), not a step that built one, however long."""
    import time

    from paddle_tpu.distributed.resilience.faults import FaultPlan, FaultSpec
    from paddle_tpu.inference import serving

    cfg, m = model
    cut, nap = 0.25, 0.3
    monkeypatch.setattr(serving, "_LONG_S", cut)
    stall = [FaultSpec("serving.stall", "stall", at=0, arg=nap)]
    eng = _engine(m)

    def long_one():
        return Request(np.arange(3, 12, dtype=np.int32), max_new_tokens=16,
                       eos_token_id=EOS, seed=1)

    with FaultPlan(specs=stall):
        _run(eng, [long_one()])                    # the first step builds
    assert eng.stats["programs_built"] > 0
    assert eng.stats["steps_over_1s"] == 0
    _run(eng, [long_one()])                        # every program met
    built = eng.stats["programs_built"]
    eng.add_request(long_one())
    eng.step()
    with FaultPlan(specs=stall):
        eng.step()
    st = eng.stats
    assert st["programs_built"] == built
    assert st["steps_over_1s"] == 1
    assert nap <= st["steps_over_1s_wall_s"] < nap + cut
    # the device was empty and the host asleep: the host held the step
    assert st["steps_over_1s_starved_s"] >= nap > st["steps_over_1s_wait_s"]
    assert st["starved_dispatch_s"] >= nap
    # a caller that stays away past the cut is counted apart
    caller = st["starved_caller_s"]
    time.sleep(nap)
    eng.step()
    assert st["caller_over_1s"] == 1 and st["caller_over_1s_s"] >= nap
    assert st["starved_caller_s"] == caller
    _run(eng, [])
    assert st["steps_over_1s"] == 1


def test_a_program_call_costs_microseconds_without_a_session(model):
    """What the ledger and ``pt.serve.call`` add to a program call with no
    profiler session and no recorder: microseconds (about 4 on this CPU).
    Counted on the calling thread's CPU clock: under six test workers the
    wall clock also counts the time the thread waits for a core (19.7 us a
    call read so where the CPU clock keeps to 3.5-3.8 with ten busy
    processes beside it), and that is no cost of the ledger's. Judged
    beside a bare call's cost in the same process, the same loop around
    the program's lookup and call without the ledger: under 10 us, or
    under 60 bare calls (some 0.13 us each) where the machine is slower."""
    import time

    import jax.numpy as jnp

    cfg, m = model
    eng = _engine(m)
    x = jnp.zeros(4).block_until_ready()
    key = ("pt_probe", (4, True))
    eng._built[key] = "4/True"
    fn = lambda: x

    def bare_call(program, k, f):
        eng._built.get((program, k))
        return f()

    def loop(call, n=2000):
        t = time.thread_time()
        for _ in range(n):
            call("pt_probe", (4, True), fn)
        return (time.thread_time() - t) / n

    # alternate, so that a burst of load falls on both
    pairs = [(loop(eng._call_built), loop(bare_call)) for _ in range(5)]
    cost, bare = (min(p[i] for p in pairs) for i in (0, 1))
    assert cost < max(10e-6, 60 * bare), (cost, bare)
    assert eng._flight.called == 10000


def _host_names(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names |= {e.name for e in line.events if e.name.startswith("pt.")}
    return names


def test_profiler_host_plane_holds_the_spans(served, tmp_path):
    """Under ``jax.profiler.start_trace`` the same spans land on the host
    plane, beside the device's."""
    import jax

    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    eng, _, _, _, _ = served
    cfg = eng.model.config
    paddle.seed(3)
    tcfg = LlamaConfig.tiny(num_hidden_layers=1)
    trainer = Engine(LlamaForCausalLM(tcfg), mesh=None, lr=1e-3)
    ids = np.random.default_rng(0).integers(
        0, tcfg.vocab_size, (2, 16)).astype(np.int32)
    jax.block_until_ready(trainer.step(ids, ids))      # built outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(eng, _requests(cfg, EOS, n=3))
        jax.block_until_ready(trainer.step(ids, ids))
    finally:
        jax.profiler.stop_trace()
    names = _host_names(str(tmp_path))
    assert {"pt.serve.step", "pt.serve.admit", "pt.serve.prefill",
            "pt.serve.decode.dispatch", "pt.serve.wait", "pt.serve.emit",
            "pt.train.step"} <= names


# ---- the names the device sees ----------------------------------------------

def _lowered(fn, *args, **kw):
    return fn.lower(*args, **kw).as_text(debug_info=True)


def test_serving_programs_carry_their_names(served):
    import jax.numpy as jnp

    eng, _, _, _, _ = served
    B = eng.max_batch
    i32 = lambda *s: jnp.zeros(s, jnp.int32)            # noqa: E731
    f32 = lambda *s: jnp.zeros(s, jnp.float32)          # noqa: E731
    kv, tables = eng.caches["kv"], eng.caches["tables"]
    text = _lowered(eng._jit_mega, eng._params, i32(B), kv, tables, i32(B),
                    jnp.zeros(B, bool), i32(B), f32(B), f32(B), i32(B),
                    n_steps=2, do_sample=True)
    assert "module @jit_pt_decode_block" in text
    for scope in ("pt.sampler", "pt.attn", "pt.mlp", "pt.lm_head",
                  "pt.kv_write"):
        assert scope + "/" in text, scope
    greedy = _lowered(eng._jit_mega, eng._params, i32(B), kv, tables, i32(B),
                      jnp.zeros(B, bool), i32(B), f32(B), f32(B), i32(B),
                      n_steps=1, do_sample=False)
    assert '"pt.sampler"' in greedy             # the all-greedy arm too
    C, P = eng._chunk_tokens, eng._maxp
    text = _lowered(eng._chunk_fn(2), eng._params, i32(2, C), kv, i32(2, P),
                    i32(2))
    assert "module @jit_pt_prefill_chunk" in text
    assert "pt.attn/pt.kv_write/" in text
    (g, _), first = next((k, fn) for k, fn in eng._jit_first.items()
                         if k[1])
    text = _lowered(first, eng._params, i32(g), kv, i32(g, P), eng._last_tok,
                    i32(g, 4), f32(g, 2))
    assert "module @jit_pt_first_token" in text and "pt.sampler/" in text
    samp = eng._dev_samp
    text = _lowered(eng._jit_apply, tables, eng._dev_pos, eng._dev_act, *samp,
                    i32(1, 1), i32(1), np.zeros(eng._upd_width, np.int32),
                    np.zeros((eng._upd_width, P), np.int32),
                    *[np.zeros(eng._upd_width, d) for d in
                      (np.int32, bool, np.int32, np.float32, np.float32,
                       np.int32)], i32(1, 1), i32(1))
    assert "module @jit_pt_slot_update" in text
    if eng._jit_cow_batch:
        w, fn = next(iter(eng._jit_cow_batch.items()))
        assert "module @jit_pt_cow_copy" in _lowered(fn, kv, i32(w), i32(w))


def test_static_pool_spec_and_reset_programs_carry_their_names(model):
    cfg, m = model
    eng = ContinuousBatchingEngine(m, max_batch=2, max_len=64, page_size=8,
                                   block_size=4)
    _run(eng, _requests(cfg, None, n=2))
    assert eng._jit_mega.__wrapped__.__name__ == "pt_decode_block"
    assert {fn.__wrapped__.__name__ for fn in eng._jit_prefill.values()} == {
        "pt_prefill_group"}
    spec = _engine(m, speculative=SpecConfig(k=2), kv_cache="int8")
    _run(spec, [Request(np.tile(np.arange(3, 9, dtype=np.int32), 3),
                        max_new_tokens=6)])
    assert spec._jit_spec.__wrapped__.__name__ == "pt_spec_block"
    assert {fn.__wrapped__.__name__ for fn in spec._jit_qreset.values()} == {
        "pt_kv_reset"}


def test_train_step_carries_its_name_and_scopes():
    import jax.numpy as jnp

    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(3)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    eng = Engine(LlamaForCausalLM(cfg), mesh=None, lr=1e-3, clip_norm=1.0)
    ids = jnp.zeros((2, 16), jnp.int32)
    text = _lowered(eng._build_step(), eng.params, eng.m, eng.v,
                    eng.step_count, ids, ids)
    assert "module @jit_pt_train_step" in text
    # forward ops under jvp(<scope>), the backward pass under
    # transpose(jvp(<scope>)): a reader finds both by the scope's name
    for scope in ("pt.attn", "pt.mlp", "pt.fused_ce"):
        assert f"/jvp({scope})/" in text, scope
        assert f"/transpose(jvp({scope}))/" in text, scope
    assert "/pt.optimizer/" in text
    eng.eval_loss(ids, ids)
    assert "module @jit_pt_eval_loss" in _lowered(
        eng._jit_loss, eng.params, ids, ids)


@pytest.mark.parametrize("kernel", ["pt_paged_decode", "pt_flash_fwd",
                                    "pt_flash_dq", "pt_flash_dkv",
                                    "pt_grouped_matmul",
                                    "pt_grouped_matmul_bwd"])
def test_pallas_kernels_are_named_in_the_source(kernel):
    """Each kernel is a ``pallas_call(name=...)`` under a scope of the same
    name (their lowering for the chip cannot run on the CPU; the AOT check
    of chipbench/scratch reads the names in the compiled program)."""
    import importlib
    import inspect

    src = "".join(inspect.getsource(importlib.import_module(
        "paddle_tpu.ops." + mod)) for mod in
        ("flash_attention", "grouped_matmul", "paged_attention"))
    assert f'with jax.named_scope("{kernel}"):' in src
    assert f'name="{kernel}",' in src
