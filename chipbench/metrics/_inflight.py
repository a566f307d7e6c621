"""Shared by the readers of the engine's in-flight ledger (not a metric).

The engine numbers its program calls and knows when the device is empty
(``paddle_tpu/inference/serving.py`` ``_InFlight``; docs/OBSERVABILITY.md
"What the device waits for"). Two kinds of reading:

**Counters, over the whole window** (``stats1 - stats0``): ``delta``.

**Spans, over the traced window**: ``pt.serve.call`` (args ``program``,
``seq``, ``drained``) is one program call, ``pt.serve.wait`` (``seq``) the
read of a value of call ``seq``. One chip runs its programs in order, so the
executions on the "XLA Modules" line are the calls in the order of ``seq``:
``pair`` lays the two sequences on one another by their NAMES (the k-th
execution of the window against the k-th call, shifted by the few calls
whose execution or whose span fell outside the trace; among the shifts
whose names agree, the one nearest in time, which a skew of milliseconds
cannot move by a whole step) and never by comparing a host time with a
device time: that comparison is what ``clock`` then checks. With the pairs:

- a *launch gap*: from the start of a call's span to the first op of its
  execution, where the device was known empty (``drained``);
- a *read-back gap*: from the last op of an execution to the end of the
  wait that read it, where that call was the newest (the device is empty
  from there on);
- a *starved interval*: from the end of such a wait to the start of the
  next call's span, the ledger's own, rebuilt from the spans;
- ``clock``: no execution may start before its call, no read may end
  before the execution it waited for; and, step by step, the
  ``starved_us`` a ``pt.serve.step`` carries (``time.perf_counter``) may
  not exceed the idle time the device plane shows inside that step.

**The two planes' clocks.** The device plane's times are the chip's own
counter, converted by the profiler; the spans are the host's clock. Where
the trace holds them, the runtime's own ``DoEnqueueProgram`` events (host
plane, stat ``run_id``) are laid against the executions of the same
``run_id`` ("XLA Modules" line): no execution starts before its enqueue
does, and one launched into an empty device starts with it, so the largest
``enqueue start - execution start`` over the window is how far the device
plane lags the host's (``enqueues``, ``clock["offset_s"]``; the chip trace
recorded in PR 24 reads 1.37 ms). The gaps are then taken with the device
plane moved by that much, and ``clock`` reports the planes as written
beside it (``raw_*``). A trace without such events (a hand-made one) is
taken as written, and the gap readers give nothing where its check fails.

Run on a trace file, this prints the account:

    python3 chipbench/metrics/_inflight.py <file.xplane.pb>
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import statistics
import sys
import traceback

if __name__ == "__main__":                 # run as a script too
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
from chipbench.harness.trace import union  # noqa: E402
from chipbench.metrics import _program  # noqa: E402

ENGINE_PHASES = ("starved_emit_s", "starved_admit_s", "starved_prefill_s",
                 "starved_dispatch_s")
MAX_SHIFT = 6           # calls whose other half may lie outside the trace
STEP_SLACK_S = 200e-6   # a step's starved_us may pass its idle time by this


# ---- counters ------------------------------------------------------------------

def delta(run, *keys):
    """{key: stats1 - stats0} of the window, or None when the program has
    not one of the keys (the parent of the PR that brought them)."""
    s0, s1 = run.window.get("stats0"), run.window.get("stats1")
    if s0 is None or s1 is None or any(
            k not in s for s in (s0, s1) for k in keys):
        return None
    return {k: s1[k] - s0[k] for k in keys}


def per_step(run, *keys):
    """Sum of the window's deltas of ``keys`` a step, in ms; None when a
    key is missing or no step ran."""
    d = delta(run, "steps", *keys)
    if d is None or d["steps"] <= 0:
        return None
    return 1e3 * sum(d[k] for k in keys) / d["steps"]


# ---- spans ---------------------------------------------------------------------

@dataclasses.dataclass
class Call:
    seq: int
    program: str
    drained: bool
    span: object                 # the pt.serve.call Span
    run: "tuple | None" = None   # (first op's start, last op's end)


def _int(v, default=None):
    try:
        return int(v)
    except (TypeError, ValueError):
        return default


def nest(spans):
    """Sort Spans by start and give each its parent, as ``_program.read``
    does for a file's: for a Program made by hand or from a dump."""
    spans.sort(key=lambda s: (s.t0, -s.t1))
    open_ = []
    for s in spans:
        while open_ and open_[-1].t1 <= s.t0:
            open_.pop()
        s.parent = open_[-1] if open_ else None
        open_.append(s)
    return spans


def calls_of(prog):
    """The window's ``pt.serve.call`` spans by ``seq``; None unless they are
    numbered one by one (a call the trace lost would shift every pair)."""
    out = []
    for s in prog.spans:
        if s.name != "pt.serve.call":
            continue
        seq = _int(s.args.get("seq"))
        if seq is None or "program" not in s.args:
            return None
        out.append(Call(seq, str(s.args["program"]),
                        bool(_int(s.args.get("drained"), 0)), s))
    out.sort(key=lambda c: c.seq)
    if not out or [c.seq for c in out] != list(
            range(out[0].seq, out[0].seq + len(out))):
        return None
    return out


def _op_extent(starts, ops, t0, t1):
    """(first op's start, last op's end) of the ops inside [t0, t1]."""
    i = bisect.bisect_left(starts, t0)
    j = bisect.bisect_right(starts, t1)
    if i >= j:
        return t0, t1
    return ops[i].t0, max(o.t1 for o in ops[i:j])


def pair(prog):
    """The window's calls, each with the extent of its execution where the
    trace holds it; None when there is nothing to pair or the names of the
    two sequences agree under no shift."""
    calls = calls_of(prog)
    if calls is None:
        return None
    ours = {"jit_" + c.program for c in calls}
    runs = [(n, t0, t1) for n, t0, t1 in prog.modules if n in ours]
    if not runs:
        return None
    best = None
    for d in range(-MAX_SHIFT, MAX_SHIFT + 1):      # runs[j] is calls[j + d]
        lo, hi = max(0, -d), min(len(runs), len(calls) - d)
        least = min(len(runs), len(calls))
        if hi - lo < max(1, least - MAX_SHIFT, 0.6 * least):
            continue
        if any(runs[j][0] != "jit_" + calls[j + d].program
               for j in range(lo, hi)):
            continue
        off = statistics.median(abs(runs[j][1] - calls[j + d].span.t0)
                                for j in range(lo, hi))
        if best is None or off < best[0]:
            best = (off, d, lo, hi)
    if best is None:
        return None
    _, d, lo, hi = best
    starts = [o.t0 for o in prog.ops]
    for j in range(lo, hi):
        calls[j + d].run = _op_extent(starts, prog.ops, runs[j][1],
                                      runs[j][2])
    return calls


def newest_waits(prog, calls):
    """[(wait Span, Call)] of the ``pt.serve.wait`` spans that read a value
    of the newest call made before they ended: where they end, the device
    is empty."""
    by_seq = {c.seq: c for c in calls}
    t0s = [c.span.t0 for c in calls]
    out = []
    for s in prog.spans:
        if s.name != "pt.serve.wait":
            continue
        c = by_seq.get(_int(s.args.get("seq")))
        if c is None:
            continue
        k = bisect.bisect_right(t0s, s.t1)          # calls begun by then
        if k and calls[k - 1].seq == c.seq:
            out.append((s, c))
    return out


@dataclasses.dataclass
class Account:
    """The traced window by the ledger's names; times in seconds."""
    calls: list
    launch: list         # (Call, gap) of the drained calls with an execution
    readback: list       # (wait Span, Call, gap) of the newest-call waits
    starved: list        # (t0, t1) rebuilt: end of such a wait to next call
    clock: dict
    idle_s: float
    unexplained: list    # (t0, t1) of idle time none of the three explains


ENQUEUE = "DoEnqueueProgram"


def _run_id(stats, smeta):
    for sbuf in stats:
        key, val = _program._stat(sbuf)
        if smeta.get(key) == "run_id":
            return _int(_program._resolve(val, smeta))
    return None


def enqueues(path):
    """{start of an execution of device 0 ("XLA Modules", device plane):
    start of the runtime's ``DoEnqueueProgram`` of the same ``run_id`` (host
    plane)}; empty where the trace names neither."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    device, ran, enq = None, {}, {}
    for num, _, v in _program._fields(space):
        if num != 1:
            continue
        pname, lines, emeta, smeta = _program._plane(v)
        m = _program.DEVICE_PLANE.match(pname)
        if m and (device is None or int(m.group(1)) < device):
            device, ran = int(m.group(1)), {}
            for lbuf in lines:
                lname, ts_ns, events = _program._line(lbuf)
                if lname != _program.MODULES_LINE:
                    continue
                for ebuf in events:
                    _, t0, _, stats = _program._event(ebuf, ts_ns)
                    rid = _run_id(stats, smeta)
                    if rid is not None:
                        ran[rid] = t0
        elif not m:
            ours = {mid for mid, (n, _) in emeta.items() if n == ENQUEUE}
            for lbuf in lines if ours else ():
                _, ts_ns, events = _program._line(lbuf)
                for ebuf in events:
                    if ebuf[0] == 0x08 and \
                            _program._varint(ebuf, 1)[0] not in ours:
                        continue
                    mid, t0, _, stats = _program._event(ebuf, ts_ns)
                    rid = _run_id(stats, smeta) if mid in ours else None
                    if rid is not None:
                        enq[rid] = min(t0, enq.get(rid, t0))
    return {ran[r]: enq[r] for r in ran if r in enq}


def account(prog, enqueued=None):
    """The Account of a trace, or None: no device ops, no ``pt.serve.call``
    spans (the program has none), or sequences that do not pair.
    ``enqueued`` (``enqueues`` of the trace's file) moves the device plane
    by the lag it measures; without it the planes are taken as written."""
    if prog is None or not prog.ops:
        return None
    calls = pair(prog)
    if calls is None:
        return None
    offset = max((h - d for d, h in (enqueued or {}).items()), default=None)
    lag = offset or 0.0
    for c in calls:
        if c.run is not None:
            c.run = (c.run[0] + lag, c.run[1] + lag)
    waits = newest_waits(prog, calls)
    launch = [(c, c.run[0] - c.span.t0) for c in calls
              if c.drained and c.run is not None]
    readback = [(w, c, w.t1 - c.run[1]) for w, c in waits
                if c.run is not None]
    by_seq = {c.seq: c for c in calls}
    starved = []
    for w, c in waits:
        nxt = by_seq.get(c.seq + 1)
        if nxt is not None and nxt.span.t0 > w.t1:
            starved.append((w.t1, nxt.span.t0))
    # the clock: every paired call, drained or not, and every such wait
    slack_l = [c.run[0] - c.span.t0 for c in calls if c.run is not None]
    slack_r = [g for _, _, g in readback]
    gaps = [(a + lag, b + lag) for a, b in _program.idle_gaps(prog)]
    first, last = prog.ops[0].t0 + lag, max(o.t1 for o in prog.ops) + lag
    over, worst = 0, 0.0
    for s in prog.spans:
        if s.name != "pt.serve.step" or "starved_us" not in s.args \
                or s.t0 < first or s.t1 > last:
            continue
        idle = sum(min(b, s.t1) - max(a, s.t0) for a, b in gaps
                   if b > s.t0 and a < s.t1)
        excess = 1e-6 * _int(s.args["starved_us"], 0) - idle
        worst = max(worst, excess)
        over += excess > STEP_SLACK_S
    clock = {"offset_s": offset,
             "launch_violations": sum(g < 0 for g in slack_l),
             "launch_least_slack_s": min(slack_l, default=None),
             "readback_violations": sum(g < 0 for g in slack_r),
             "readback_least_slack_s": min(slack_r, default=None),
             # the planes as written: an execution lag s later, a read lag
             # s sooner after its execution
             "raw_launch_violations": sum(g < lag for g in slack_l),
             "raw_launch_least_slack_s":
                 min(slack_l) - lag if slack_l else None,
             "raw_readback_violations": sum(g + lag < 0 for g in slack_r),
             "raw_readback_least_slack_s":
                 min(slack_r) + lag if slack_r else None,
             "steps_starved_past_idle": over,
             "step_worst_excess_s": worst}
    explained = union(
        starved
        + [(c.span.t0, c.run[0]) for c in calls
           if c.run is not None and c.run[0] > c.span.t0]
        + [(c.run[1], w.t1) for w, c, g in readback if g > 0])
    unexplained = _minus(gaps, explained)
    return Account(calls=calls, launch=launch, readback=readback,
                   starved=starved, clock=clock,
                   idle_s=sum(b - a for a, b in gaps),
                   unexplained=unexplained)


def _minus(gaps, cover):
    """What is left of the sorted, disjoint ``gaps`` outside the sorted,
    disjoint ``cover``."""
    out, i = [], 0
    for a, b in gaps:
        while i < len(cover) and cover[i][1] <= a:
            i += 1
        j, at = i, a
        while j < len(cover) and cover[j][0] < b:
            if cover[j][0] > at:
                out.append((at, cover[j][0]))
            at = max(at, cover[j][1])
            j += 1
        if at < b:
            out.append((at, b))
    return out


_CACHE = {}


def of(run):
    """The Account of a run's trace (one a trace file), or None."""
    prog = _program.of(run)
    if prog is None:
        return None
    if _CACHE.get("prog") is not prog:
        _CACHE.clear()
        try:
            acc = account(prog, _enqueued(prog))
        except (ArithmeticError, LookupError, TypeError, ValueError):
            # a trace laid out as none seen so far: the run goes on without
            # these metrics, and says why
            traceback.print_exc()
            acc = None
        _CACHE.update(prog=prog, account=acc)
    return _CACHE["account"]


def _enqueued(prog):
    if not prog.path:
        return None
    try:
        return enqueues(prog.path)
    except (OSError, ValueError, IndexError):
        return None


def clock_holds(acc) -> bool:
    return (acc.clock["launch_violations"] == 0
            and acc.clock["readback_violations"] == 0)


def mean_gap_ms(gaps):
    return 1e3 * statistics.fmean(gaps) if gaps else None


# ---- the account, printed --------------------------------------------------------

def _neighbours(prog, t0, t1):
    """The modules that ran before and after an idle interval."""
    ends = [m[2] for m in prog.modules]
    i = bisect.bisect_right(ends, t0 + 1e-9)
    before = prog.modules[i - 1][0] if i else "(start)"
    after = next((m[0] for m in prog.modules[i:] if m[1] >= t1 - 1e-9),
                 "(end)")
    return before.removeprefix("jit_"), after.removeprefix("jit_")


def describe(prog) -> str:
    acc = account(prog, _enqueued(prog))
    if acc is None:
        return "nothing to read: no pt.serve.call spans pair with the " \
               "executions of this trace"
    out = []
    span = prog.ops[-1].t1 - prog.ops[0].t0
    paired = [c for c in acc.calls if c.run is not None]
    out.append(f"calls {len(acc.calls)} (seq {acc.calls[0].seq}.."
               f"{acc.calls[-1].seq}), paired {len(paired)}; device span "
               f"{span * 1e3:.1f} ms, idle {acc.idle_s * 1e3:.2f} ms "
               f"({100 * acc.idle_s / max(span, 1e-12):.2f}%)")
    out.append("clock: " + ", ".join(
        f"{k} {v * 1e3:.4f} ms" if k.endswith("_s") and v is not None
        else f"{k} {v}" for k, v in acc.clock.items()))

    def by_program(rows):
        tot = {}
        for c, g in rows:
            tot.setdefault(c.program, []).append(g)
        return "; ".join(
            f"{p} x{len(g)} mean {1e3 * statistics.fmean(g):.3f} "
            f"min {1e3 * min(g):.3f} max {1e3 * max(g):.3f} ms "
            f"(sum {1e3 * sum(g):.2f})"
            for p, g in sorted(tot.items(), key=lambda kv: -sum(kv[1])))

    out.append("launch gaps (drained calls): " + by_program(acc.launch))
    out.append("launch gaps (calls with the device maybe busy): "
               + by_program([(c, c.run[0] - c.span.t0) for c in paired
                             if not c.drained]))
    out.append("read-back gaps: " + by_program(
        [(c, g) for _, c, g in acc.readback]))
    st = sum(b - a for a, b in acc.starved)
    steps = [s for s in prog.spans if s.name == "pt.serve.step"
             and "starved_us" in s.args]
    out.append(
        f"starved intervals rebuilt from the spans: {len(acc.starved)}, "
        f"{st * 1e3:.2f} ms; the {len(steps)} steps' own starved_us "
        f"{sum(_int(s.args['starved_us'], 0) for s in steps) * 1e-3:.2f} ms,"
        f" maybe_starved_us "
        f"{sum(_int(s.args.get('maybe_starved_us'), 0) for s in steps) * 1e-3:.2f}"
        f" ms, wait_us "
        f"{sum(_int(s.args.get('wait_us'), 0) for s in steps) * 1e-3:.2f} ms")
    by_phase = {}
    for a, b in acc.starved:
        # split at span boundaries, as the engine does
        cuts = sorted({a, b} | {t for s in prog.spans for t in (s.t0, s.t1)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            s = _program.innermost(prog, 0.5 * (x + y))
            while s is not None and s.name in (
                    "pt.serve.wait", "pt.serve.call", "pt.serve.build"):
                s = s.parent
            k = s.name if s else "(caller)"
            by_phase[k] = by_phase.get(k, 0.0) + (y - x)
    out.append("  by phase: " + "; ".join(
        f"{k} {v * 1e3:.2f} ms" for k, v in
        sorted(by_phase.items(), key=lambda kv: -kv[1])))
    un = sum(b - a for a, b in acc.unexplained)
    out.append(f"idle none of them explains: {un * 1e3:.2f} ms, "
               f"{100 * un / max(acc.idle_s, 1e-12):.2f}% of idle; by the "
               f"programs on either side:")
    sides = {}
    for a, b in acc.unexplained:
        k = _neighbours(prog, a, b)
        t, n = sides.get(k, (0.0, 0))
        sides[k] = (t + b - a, n + 1)
    out += [f"  {k[0]} -> {k[1]}: {t * 1e3:.2f} ms in {n}"
            for k, (t, n) in sorted(sides.items(),
                                    key=lambda kv: -kv[1][0])[:8]]
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(_program.read(sys.argv[1])))
