"""``metrics/_inflight.py`` and the seven readers of the engine's in-flight
ledger: on hand-made counters and a hand-made trace, whose answers are
known, and on the trace recorded on the chip by
``scratch/record_inflight_trace.py``."""

import gzip
import os
import types

import pytest

from chipbench.harness import loader
from chipbench.metrics import _inflight, _program
from chipbench.metrics._program import Op, Program, Span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3
SPAN_READERS = ("launch_gap_ms", "readback_gap_ms", "idle_unexplained_share")
COUNTER_READERS = ("device_starved_share", "starved_step_ms",
                   "starved_caller_ms", "stalled_steps")


def _reader(name):
    return loader._module("metrics", name, name)


def _run(stats0=None, stats1=None, window_s=50.0, cell="x"):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=cell), trace=object(),
        window={"stats0": stats0 or {}, "stats1": stats1 or {},
                "window_s": window_s})


# ---- counters ------------------------------------------------------------------

S0 = {"steps": 100, "device_starved_s": 1.0, "starved_emit_s": 0.1,
      "starved_admit_s": 0.2, "starved_prefill_s": 0.3,
      "starved_dispatch_s": 0.1, "starved_caller_s": 0.3,
      "device_maybe_starved_s": 0.5, "drains": 150, "caller_over_1s": 0,
      "caller_over_1s_s": 0.0, "steps_over_1s": 1}
S1 = {"steps": 600, "device_starved_s": 6.0, "starved_emit_s": 0.6,
      "starved_admit_s": 1.2, "starved_prefill_s": 1.8,
      "starved_dispatch_s": 0.6, "starved_caller_s": 1.8,
      "device_maybe_starved_s": 2.5, "drains": 1100, "caller_over_1s": 1,
      "caller_over_1s_s": 10.0, "steps_over_1s": 3}


def test_counter_readers_known_answers():
    run = _run(S0, S1, window_s=60.0)
    # 5 s starved of 60 s less the 10 s the caller was away
    assert _reader("device_starved_share").read(run) == pytest.approx(10.0)
    # (0.5 + 1.0 + 1.5 + 0.5) s over 500 steps
    assert _reader("starved_step_ms").read(run) == pytest.approx(7.0)
    assert _reader("starved_caller_ms").read(run) == pytest.approx(3.0)
    assert _reader("stalled_steps").read(run) == 2.0


@pytest.mark.parametrize("metric", COUNTER_READERS)
def test_counter_readers_find_nothing_where_a_key_is_missing(metric):
    """The parent's stats hold none of the keys; a program that lost one
    gives nothing either, and nothing is raised."""
    parent = _run({"steps": 1, "hit_tokens": 1}, {"steps": 9, "hit_tokens": 2})
    assert _reader(metric).read(parent) is None
    for key in S0:
        if key in ("device_maybe_starved_s", "drains", "caller_over_1s"):
            continue                         # no metric reads these
        s0 = {k: v for k, v in S0.items() if k != key}
        needs = {"device_starved_share": ("device_starved_s",
                                          "caller_over_1s_s"),
                 "starved_step_ms": ("steps",) + _inflight.ENGINE_PHASES,
                 "starved_caller_ms": ("steps", "starved_caller_s"),
                 "stalled_steps": ("steps_over_1s",)}[metric]
        got = _reader(metric).read(_run(s0, S1))
        assert (got is None) == (key in needs), (metric, key)
    assert _reader(metric).read(types.SimpleNamespace(window={})) is None


def test_counter_readers_find_nothing_in_a_window_without_steps():
    idle = _run(S0, dict(S0))
    assert _reader("starved_step_ms").read(idle) is None
    assert _reader("starved_caller_ms").read(idle) is None
    assert _reader("device_starved_share").read(idle) == 0.0
    assert _reader("stalled_steps").read(idle) == 0.0
    assert _reader("device_starved_share").read(
        _run(S0, S1, window_s=10.0)) is None     # away all of the window


# ---- a trace written by hand -----------------------------------------------------

def _known(host_shift_ms=0.0, starved_us=6200):
    """One chip, two engine steps, times in ms.

    Device: a decode block whose call predates the trace 5-9; then the
    calls 10.. in order: ``pt_slot_update`` 10.5-10.52, ``pt_decode_block``
    11.3-21.3, ``pt_prefill_chunk`` 25.6-33.6, ``pt_first_token`` 33.6-36.6,
    ``pt_slot_update`` 43.5-43.52, ``pt_decode_block`` 44.3-54.3.

    Host, step 1 10-40: dispatch 10-11.2 (call 10 at 10.2, drained; call 11
    at 10.9), wait(11) 11.2-21.8, emit 21.8-23, admit 23-24, prefill
    24-39.5 (call 12 at 25.0, drained; call 13 at 26.0; wait(13) 26.3-37;
    emit 37-39). Step 2 43-56: dispatch 43-44.2 (call 14 at 43.2, drained;
    call 15 at 43.9), wait(15) 44.2-54.9.

    So: launch gaps of the drained calls 0.3, 0.6, 0.3; read-back gaps 0.5,
    0.4, 0.6; starved 21.8-25.0 and 37.0-43.2; idle 9-10.5, 10.52-11.3,
    21.3-25.6, 36.6-43.5, 43.52-44.3 = 14.26, of which 9-10.2, 10.52-10.9
    and 43.52-43.9 = 1.96 are explained by none."""
    ops, modules = [], []

    def ran(name, a, b):
        modules.append(("jit_" + name, a * MS, b * MS))
        mid = 0.5 * (a + b)
        ops.extend([Op("%fusion.1", f"jit({name})/pt.attn/dot", a * MS,
                       mid * MS),
                    Op("%fusion.2", f"jit({name})/pt.mlp/dot", mid * MS,
                       b * MS)])

    ran("pt_decode_block", 5, 9)
    ran("pt_slot_update", 10.5, 10.52)
    ran("pt_decode_block", 11.3, 21.3)
    ran("pt_prefill_chunk", 25.6, 33.6)
    ran("pt_first_token", 33.6, 36.6)
    ran("pt_slot_update", 43.5, 43.52)
    ran("pt_decode_block", 44.3, 54.3)
    modules.append(("jit_convert_element_type", 54.3 * MS, 54.3 * MS))
    h = host_shift_ms
    spans = []

    def span(name, a, b, **args):
        spans.append(Span("pt.serve." + name, (a + h) * MS, (b + h) * MS,
                          args))

    def call(seq, program, drained, a):
        span("call", a, a + 0.2, program=program, seq=seq, drained=drained,
             key="8/True")

    span("step", 10, 40, step=1, starved_us=starved_us, maybe_starved_us=700,
         wait_us=21300)
    span("decode.dispatch", 10, 11.2)
    call(10, "pt_slot_update", 1, 10.2)
    call(11, "pt_decode_block", 0, 10.9)
    span("wait", 11.2, 21.8, what="decode_block", seq=11)
    span("emit", 21.8, 23)
    span("admit", 23, 24)
    span("prefill", 24, 39.5)
    call(12, "pt_prefill_chunk", 1, 25.0)
    call(13, "pt_first_token", 0, 26.0)
    span("wait", 26.3, 37, what="first_token", seq=13)
    span("emit", 37, 39)
    span("step", 43, 56, step=2, starved_us=200, maybe_starved_us=700,
         wait_us=10700)
    span("decode.dispatch", 43, 44.2)
    call(14, "pt_slot_update", 1, 43.2)
    call(15, "pt_decode_block", 0, 43.9)
    span("wait", 44.2, 54.9, what="decode_block", seq=15)
    ops.sort(key=lambda o: (o.t0, -o.t1))
    modules.sort(key=lambda m: m[1])
    return Program(ops=ops, modules=modules, spans=_inflight.nest(spans))


@pytest.fixture()
def trace(monkeypatch):
    """``use(prog)`` makes ``prog`` the trace the readers find."""
    def use(prog):
        _inflight._CACHE.clear()
        monkeypatch.setattr(_program, "of", lambda run: prog)
    return use


def test_calls_pair_with_executions_by_name_and_order():
    calls = _inflight.pair(_known())
    assert [c.seq for c in calls] == list(range(10, 16))
    assert [c.drained for c in calls] == [True, False] * 3
    assert [round(c.run[0] / MS, 2) for c in calls] == [
        10.5, 11.3, 25.6, 33.6, 43.5, 44.3]
    assert calls[1].run == pytest.approx((11.3 * MS, 21.3 * MS))
    # a host plane 5 ms late pairs the same: names decide, not times
    late = _inflight.pair(_known(host_shift_ms=5.0))
    assert [c.run for c in late] == [c.run for c in calls]
    # a call the trace lost would shift every pair: nothing is paired
    prog = _known()
    prog.spans = [s for s in prog.spans if s.args.get("seq") != 12
                  or s.name != "pt.serve.call"]
    assert _inflight.pair(prog) is None
    # and where no shift makes the names agree, nothing either
    prog = _known()
    prog.modules[2] = ("jit_pt_first_token",) + prog.modules[2][1:]
    assert _inflight.pair(prog) is None


def test_account_of_the_known_trace():
    prog = _known()
    acc = _inflight.account(prog)
    assert [(c.seq, pytest.approx(g / MS)) for c, g in acc.launch] == [
        (10, 0.3), (12, 0.6), (14, 0.3)]
    assert [(c.seq, pytest.approx(g / MS)) for _, c, g in acc.readback] == [
        (11, 0.5), (13, 0.4), (15, 0.6)]
    assert acc.starved == [pytest.approx((21.8 * MS, 25.0 * MS)),
                           pytest.approx((37.0 * MS, 43.2 * MS))]
    assert acc.idle_s == pytest.approx(14.26 * MS)
    assert [pytest.approx((a / MS, b / MS)) for a, b in acc.unexplained] == [
        (9, 10.2), (10.52, 10.9), (43.52, 43.9)]
    assert acc.clock == {
        "offset_s": None,
        "launch_violations": 0,
        "launch_least_slack_s": pytest.approx(0.3 * MS),
        "readback_violations": 0,
        "readback_least_slack_s": pytest.approx(0.4 * MS),
        "raw_launch_violations": 0,
        "raw_launch_least_slack_s": pytest.approx(0.3 * MS),
        "raw_readback_violations": 0,
        "raw_readback_least_slack_s": pytest.approx(0.4 * MS),
        # step 1 was starved 6.2 ms and the device shows 8.98 ms idle in it;
        # step 2 ends after the device's last op and is not held to it
        "steps_starved_past_idle": 0,
        "step_worst_excess_s": 0.0}
    text = _inflight.describe(prog)
    assert "pt_prefill_chunk x1 mean 0.600" in text
    assert "pt_decode_block -> pt_slot_update" in text


def test_span_readers_known_answers(trace):
    trace(_known())
    run = _run()
    assert _reader("launch_gap_ms").read(run) == pytest.approx(0.4)
    assert _reader("readback_gap_ms").read(run) == pytest.approx(0.5)
    assert _reader("idle_unexplained_share").read(run) == pytest.approx(
        100 * 1.96 / 14.26)


def test_the_clock_check_refuses_a_host_plane_5_ms_late(trace):
    prog = _known(host_shift_ms=5.0)
    acc = _inflight.account(prog)
    # every execution but the queued first token's now starts before its
    # call's span does
    assert acc.clock["launch_violations"] == 5
    assert acc.clock["launch_least_slack_s"] == pytest.approx(-4.7 * MS)
    assert acc.clock["readback_violations"] == 0
    assert not _inflight.clock_holds(acc)
    trace(prog)
    assert _reader("launch_gap_ms").read(_run()) is None
    assert _reader("readback_gap_ms").read(_run()) is None
    # a host plane 5 ms EARLY shows in the reads
    acc = _inflight.account(_known(host_shift_ms=-5.0))
    assert acc.clock["readback_violations"] == 3
    assert acc.clock["launch_violations"] == 0


def test_the_runtimes_enqueues_measure_the_device_planes_lag(trace):
    """A device plane 1.4 ms behind the host's, as the chip's traces are:
    the planes as written fail the check. The runtime's enqueue events
    (here 0.1 ms into each call's span, so 0.2 ms before the execution of a
    call into an empty device, as the trace was made) measure the lag to
    within that 0.2 ms, the device plane is moved by it, and the gaps come
    out as they were made but for 0.2 ms that pass from each launch to
    each read."""
    prog = _known(host_shift_ms=1.4)
    raw = _inflight.account(prog)
    assert raw.clock["offset_s"] is None
    assert raw.clock["launch_violations"] == 5
    calls = _inflight.pair(_known(host_shift_ms=1.4))
    # execution start (device plane) -> enqueue start (host plane): 0.1 ms
    # into each call's span
    enq = {c.run[0]: c.span.t0 + 0.1 * MS for c in calls}
    acc = _inflight.account(prog, enq)
    # the slot updates' executions start 0.2 ms after their enqueues (as
    # made); on the planes as written 1.2 ms before: the lag read is 1.2
    assert acc.clock["offset_s"] == pytest.approx(1.2 * MS)
    assert acc.clock["launch_violations"] == 0
    assert acc.clock["readback_violations"] == 0
    assert acc.clock["raw_launch_violations"] == 5
    assert acc.clock["raw_launch_least_slack_s"] == pytest.approx(-1.1 * MS)
    assert _inflight.clock_holds(acc)
    # 0.2 ms of each launch is now read as the values' way back
    assert [pytest.approx(g / MS) for _, g in acc.launch] == [0.1, 0.4, 0.1]
    assert [pytest.approx(g / MS) for _, _, g in acc.readback] == [
        0.7, 0.6, 0.8]
    assert sum(g for _, g in acc.launch) / 3 + sum(
        g for _, _, g in acc.readback) / 3 == pytest.approx(0.9 * MS)
    assert acc.idle_s == pytest.approx(14.26 * MS)


def test_enqueues_of_the_chip_trace_recorded_in_pr_24():
    """Three executions, three ``DoEnqueueProgram`` of the same run ids: the
    device plane starts each 1.36-1.37 ms BEFORE the runtime enqueued it."""
    lags = sorted(h - d for d, h in _inflight.enqueues(
        os.path.join(DATA, "scoped.xplane.pb")).items())
    assert [round(x / MS, 3) for x in lags] == [1.357, 1.370, 1.373]


def test_a_step_starved_for_longer_than_the_device_idled_is_told():
    acc = _inflight.account(_known(starved_us=9500))
    assert acc.clock["steps_starved_past_idle"] == 1
    assert acc.clock["step_worst_excess_s"] == pytest.approx(0.52 * MS)


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_span_readers_find_nothing_without_the_spans(trace, metric):
    """The parent's trace has no ``pt.serve.call``; a run may have no
    trace at all: nothing to read, nothing raised."""
    prog = _known()
    prog.spans = [s for s in prog.spans if s.name != "pt.serve.call"]
    trace(prog)
    assert _reader(metric).read(_run()) is None
    trace(None)
    assert _reader(metric).read(_run()) is None
    prog = _known()
    prog.ops = []
    trace(prog)
    assert _reader(metric).read(_run()) is None


# ---- the trace recorded on the chip ------------------------------------------

@pytest.fixture()
def chip(tmp_path, monkeypatch):
    """The engine's own spans at a toy size, recorded on a v5e by
    ``scratch/record_inflight_trace.py`` (kept gzipped: 2.4 MB of HLO text
    in the event names), as the trace of a cell named ``chip``."""
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    _inflight._CACHE.clear()
    d = tmp_path / ".chipbench_trace" / "chip" / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "inflight.xplane.pb.gz")) as f:
        (d / "vm.xplane.pb").write_bytes(f.read())
    return _program.of(_run(cell="chip"))


def test_chip_trace_calls_pair_with_the_executions(chip):
    calls = _inflight.pair(chip)
    assert [c.seq for c in calls] == list(range(34, 51))
    assert all(c.run is not None for c in calls)
    assert [m[0] for m in chip.modules] == ["jit_" + c.program for c in calls]
    assert {c.program for c in calls} == {
        "pt_cow_copy", "pt_prefill_chunk", "pt_first_token", "pt_slot_update",
        "pt_decode_block"}
    # the first program of every step but the first is called with the
    # device known empty; the block behind a slot update never is
    assert [c.program for c in calls if c.drained] == [
        "pt_slot_update", "pt_slot_update", "pt_prefill_chunk",
        "pt_slot_update", "pt_slot_update", "pt_slot_update",
        "pt_decode_block", "pt_slot_update"]
    waits = _inflight.newest_waits(chip, calls)
    assert [(w.args["what"], c.program) for w, c in waits] == [
        ("first_token", "pt_first_token"), ("decode_block", "pt_decode_block"),
        ("decode_block", "pt_decode_block"), ("first_token", "pt_first_token"),
    ] + [("decode_block", "pt_decode_block")] * 4
    steps = [s for s in chip.spans if s.name == "pt.serve.step"]
    assert [s.args["starved_us"] for s in steps] == [238, 963, 61, 61, 58, 109]


def test_chip_trace_clock_the_device_plane_lags_by_1_4_ms(chip):
    """On the planes as written 11 of 17 executions start BEFORE their
    calls; every one of them starts 1.34-1.38 ms before the runtime
    enqueued it. Moved by that lag, nothing is out of order."""
    raw = _inflight.account(chip)
    assert raw.clock["offset_s"] is None
    assert raw.clock["launch_violations"] == 11
    assert raw.clock["launch_least_slack_s"] == pytest.approx(-0.99934e-3,
                                                              rel=1e-4)
    assert not _inflight.clock_holds(raw)
    enq = _inflight.enqueues(chip.path)
    assert len(enq) == 17
    lags = sorted(h - d for d, h in enq.items())
    assert 1.2e-3 < lags[0] and lags[-1] == pytest.approx(1.376096e-3,
                                                          rel=1e-5)
    acc = _inflight.account(chip, enq)
    assert acc.clock["offset_s"] == pytest.approx(lags[-1])
    assert acc.clock["launch_violations"] == 0
    assert acc.clock["readback_violations"] == 0
    assert acc.clock["raw_launch_violations"] == 11
    assert acc.clock["steps_starved_past_idle"] == 0
    assert _inflight.clock_holds(acc)
    # a slot update's 15 small host arrays take 1.8-2.1 ms to reach the
    # device; a call whose arguments are there already, 0.4-0.7
    slot = [g for c, g in acc.launch if c.program == "pt_slot_update"]
    rest = [g for c, g in acc.launch if c.program != "pt_slot_update"]
    assert len(slot) == 6 and all(1.8e-3 < g < 2.1e-3 for g in slot)
    assert len(rest) == 2 and all(0.3e-3 < g < 0.7e-3 for g in rest)
    assert all(0.6e-3 < g < 1.1e-3 for _, _, g in acc.readback)


def test_chip_trace_readers(chip):
    run = _run(cell="chip")
    assert _reader("launch_gap_ms").read(run) == pytest.approx(
        CHIP["launch_gap_ms"], rel=1e-6)
    assert _reader("readback_gap_ms").read(run) == pytest.approx(
        CHIP["readback_gap_ms"], rel=1e-6)
    # a toy engine with a 3 ms sleep as its caller: most of the idle time
    # lies between the releases finished() lands and the next step's call
    assert _reader("idle_unexplained_share").read(run) == pytest.approx(
        CHIP["idle_unexplained_share"], rel=1e-6)
    text = _inflight.describe(chip)
    assert "offset_s 1.3761 ms" in text
    assert "pt_slot_update -> pt_slot_update" in text


# what the readers give for the committed file (recorded by my chip run,
# PR 38, call 7: record_inflight_trace.py printed the same account there)
CHIP = {"launch_gap_ms": 1.5912416504999995,
        "readback_gap_ms": 0.8314132987500026,
        "idle_unexplained_share": 37.26868057067153}
