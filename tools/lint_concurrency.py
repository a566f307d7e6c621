"""Concurrency-lint gate: sweep the whole package with the PT-RACE
analyzer (paddle_tpu/static/concurrency — docs/STATIC_ANALYSIS.md).

The graph got a linter in PR 1 (tools/lint_graph.py); this is the same
gate for the threaded HOST stack — supervisors, watchdogs, metrics/HTTP
servers, heartbeat loops, async checkpoint writers. Pure AST: analyzed
modules are never imported, so the sweep is fast and side-effect free.

Exit code 0 iff every error-severity finding is either absent or covered
by the reviewed baseline file (tools/concurrency_baseline.json — one
entry per finding id WITH a justification string; an unreviewed defect
can only make the gate red, never silently pass).

Usage:
    python tools/lint_concurrency.py                  # full package gate
    python tools/lint_concurrency.py paddle_tpu/inference
    python tools/lint_concurrency.py --fail-on warning
    python tools/lint_concurrency.py --inject unguarded_write
    python tools/lint_concurrency.py --selftest       # all 5 PT-RACE classes
    python tools/lint_concurrency.py --write-baseline # refresh (review it!)

``--inject`` lints one fixture module seeded with a known defect class and
must flip the exit code; ``--selftest`` loops every class in-process plus a
clean fixture, exiting 0 iff each one was detected with its expected code —
both pinned in tests/test_ci_gates.py beside lint_graph / fault_drill /
scrape_metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import _selftest

ROOT = _selftest.bootstrap()

BASELINE_PATH = os.path.join(ROOT, "tools", "concurrency_baseline.json")

#: cross-module thread entry points the per-module AST cannot see —
#: PUBLIC APIs that run on threads started elsewhere. Root only entry
#: points (never private helpers: rooting a helper disables the
#: caller-held-lock inheritance that proves it clean under its callers'
#: locks). Reviewed alongside the baseline file.
_TRACER_API = ["TraceRecorder." + m for m in (
    "submit", "shed", "admit", "prefill_chunk", "first_token", "tokens",
    "tokens_batch", "first_tokens", "finish", "mark_recovered", "failover",
    "migrate",
    "migration_failure", "recovery", "publish", "resume", "instant",
    "span", "is_open", "incomplete", "lifecycle", "export_chrome",
    "slo_summary", "counters")]

THREAD_ROOTS = {
    # fleet parallel_step replica threads, the rpc ThreadPoolExecutor and
    # the elastic heartbeat daemon all funnel through retry_call
    "paddle_tpu/distributed/resilience/retry.py": ["retry_call"],
    # ONE TraceRecorder is stamped from every replica's step thread under
    # FleetConfig(parallel_step=True) while the driver reads exports
    "paddle_tpu/observability/tracing.py": _TRACER_API,
    # the MetricsServer scrape thread walks the registry while engine
    # threads record into the instruments
    "paddle_tpu/observability/metrics.py": [
        "MetricsRegistry.collect", "MetricsRegistry.dump",
        "_Instrument.family", "Histogram.family",
        "Counter.inc", "Gauge.set", "Histogram.observe",
        "Counter.value", "Gauge.value", "Histogram.count",
        "Histogram.quantile"],
    # ParameterServer methods execute on rpc handler threads
    "paddle_tpu/distributed/ps/__init__.py": [
        "ParameterServer.create_dense_table",
        "ParameterServer.create_sparse_table",
        "ParameterServer.pull_dense", "ParameterServer.push_dense",
        "ParameterServer.pull_sparse", "ParameterServer.push_sparse",
        "ParameterServer.stat"],
    "paddle_tpu/distributed/ps/_tables.py": [
        "DenseTable.pull", "DenseTable.push", "DenseTable.stat",
        "SparseTable.pull", "SparseTable.push", "SparseTable.stat"],
    # TCPStore client ops run on the elastic heartbeat thread beside the
    # main path
    "paddle_tpu/distributed/communication/store.py": [
        "TCPStore.add", "TCPStore.get"],
    # procfleet (docs/SERVING.md "Process fleet"): the proxy's heartbeat
    # thread and the fleet's parallel_step replica threads both drive the
    # wire helpers, and parallel_step threads enter the proxy through its
    # public replica surface (step/submit/progress/load run concurrently
    # with the driver reading finished()/metrics)
    "paddle_tpu/inference/procfleet/wire.py": ["send_msg", "recv_msg"],
    "paddle_tpu/inference/procfleet/proxy.py": [
        "ProcReplica.step", "ProcReplica.submit", "ProcReplica.progress",
        "ProcReplica.load", "ProcReplica.has_work", "ProcReplica.behind",
        "ProcReplica.heartbeat_count"],
    # the transport seam (docs/SERVING.md "Transport seam"): frame IO is
    # driven from the heartbeat thread and parallel_step replica threads
    # (serialized per proxy by its _io_lock), and the loopback worker is
    # a daemon THREAD whose entry point replaces the spawned process
    "paddle_tpu/inference/procfleet/transport.py": [
        "TcpTransport.send_frame", "TcpTransport.recv_frame",
        "LoopbackTransport.send_frame", "LoopbackTransport.recv_frame",
        "ChaosTransport.send_frame", "ChaosTransport.recv_frame"],
    "paddle_tpu/inference/procfleet/worker.py": ["worker_thread_main"],
}


# ---------------------------------------------------------------------------
# seeded-defect fixtures (one module per PT-RACE class + one clean)
# ---------------------------------------------------------------------------

FIXTURES = {
    "unguarded_write": '''
import threading

class Poller:
    def __init__(self):
        self.hits = 0
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            self.hits += 1          # worker increments...

    def snapshot(self):
        out = self.hits             # ...main reads AND resets, no lock
        self.hits = 0
        return out
''',
    "inconsistent_guard": '''
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}
        threading.Thread(target=self._refresh, daemon=True).start()

    def _refresh(self):
        while True:
            with self._lock:
                self._entries["ts"] = 1

    def invalidate(self):
        self._entries.clear()       # everywhere else holds _lock
''',
    "lock_order": '''
import threading

class Transfer:
    def __init__(self):
        self._alock = threading.Lock()
        self._block = threading.Lock()
        self.a = 0
        self.b = 0
        threading.Thread(target=self._rebalance, daemon=True).start()

    def _rebalance(self):
        with self._block:           # B then A...
            with self._alock:
                self.a += 1
                self.b -= 1

    def move(self):
        with self._alock:           # ...A then B: inversion
            with self._block:
                self.a -= 1
                self.b += 1
''',
    "check_then_act": '''
import threading

class JobQueue:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = []
        threading.Thread(target=self._drain, daemon=True).start()

    def _drain(self):
        while True:
            if self._q:             # checked OUTSIDE the lock...
                with self._lock:
                    self._q.pop()   # ...acted on under it: stale decision

    def put(self, x):
        with self._lock:
            self._q.append(x)
''',
    "thread_leak": '''
import threading

def _writer(path):
    with open(path, "w") as f:
        f.write("x")

def export_logs(path):
    t = threading.Thread(target=_writer, args=(path,))
    t.start()                       # non-daemon, never joined anywhere
''',
}

EXPECTED_CODE = {
    "unguarded_write": "PT-RACE-001",
    "inconsistent_guard": "PT-RACE-002",
    "lock_order": "PT-RACE-003",
    "check_then_act": "PT-RACE-004",
    "thread_leak": "PT-RACE-005",
}

CLEAN_FIXTURE = '''
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._jobs = []
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            with self._lock:
                if self._stop:
                    return
                if self._jobs:
                    self._jobs.pop()

    def put(self, x):
        with self._lock:
            self._jobs.append(x)

    def close(self):
        with self._lock:
            self._stop = True
        self._thread.join(timeout=1.0)
'''


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str = BASELINE_PATH):
    """{finding_id: justification}. Entries WITHOUT a justification are
    rejected — the file is a review record, not a mute button."""
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    out = {}
    for entry in doc.get("entries", ()):
        fid = entry.get("id")
        just = (entry.get("justification") or "").strip()
        if not fid or not just:
            raise SystemExit(
                f"baseline entry {entry!r} is missing an id or a "
                "justification — every suppression must say why")
        out[fid] = just
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_gate(paths, fail_on="error", baseline=None, verbose=False,
             use_roots=True):
    """Sweep ``paths``; returns (exit_code, report, gate_findings)."""
    from paddle_tpu.static.analysis import Severity
    from paddle_tpu.static.concurrency import analyze_paths

    report, analyzed = analyze_paths(
        paths, base=ROOT, thread_roots=THREAD_ROOTS if use_roots else {})
    floor = Severity.ERROR if fail_on == "error" else Severity.WARNING
    baseline = baseline if baseline is not None else {}
    gate, suppressed = [], []
    for d in report.at_least(floor):
        fid = getattr(d, "finding_id", None)
        if fid in baseline:
            suppressed.append(d)
        else:
            gate.append(d)
    shown = list(report) if verbose else gate
    for d in shown:
        fid = getattr(d, "finding_id", "")
        print(f"{d.format()}\n    id: {fid}")
    for d in suppressed:
        print(f"[baselined] {getattr(d, 'finding_id', '')}: "
              f"{baseline[getattr(d, 'finding_id', '')]}")
    stale = sorted(set(baseline) - {
        getattr(d, "finding_id", None) for d in report})
    for fid in stale:
        print(f"[stale baseline entry — remove it] {fid}")
    status = "FINDINGS AT GATE SEVERITY" if gate else "CLEAN"
    print(f"CONCURRENCY LINT {'FAIL' if gate else 'OK'}: "
          f"{len(analyzed)} module(s), {len(report)} finding(s), "
          f"{len(suppressed)} baselined, {len(gate)} at gate severity — "
          f"{status}")
    return (1 if gate else 0), report, gate


def selftest():
    """Every seeded PT-RACE class must be detected with its expected code
    at error severity; the clean fixture must lint clean; one end-to-end
    --inject arm pins the exit-code flip itself."""
    from paddle_tpu.static.concurrency import analyze_source

    h = _selftest.Harness("CONCURRENCY")
    rep = analyze_source(CLEAN_FIXTURE, "fixtures/clean.py")
    h.case("clean fixture", not rep.errors(),
           f"{len(rep)} finding(s), {len(rep.errors())} error(s)")
    for defect, src in FIXTURES.items():
        want = EXPECTED_CODE[defect]
        rep = analyze_source(src, f"fixtures/{defect}.py")
        hit = [d for d in rep.errors() if d.code == want]
        if hit:
            h.case(f"inject {defect}", True,
                   f"detected {want} — {hit[0].message[:70]}")
        else:
            h.case(f"inject {defect}", False,
                   f"wanted {want}, got {[d.code for d in rep]}")
    # end-to-end: the same defect through the real gate driver must flip
    # the exit code, and a baseline entry for it must un-flip it
    import tempfile

    with tempfile.TemporaryDirectory(dir=ROOT) as tmp:
        bad = os.path.join(tmp, "seeded.py")
        with open(bad, "w") as f:
            f.write(FIXTURES["unguarded_write"])
        rc_bad, report, gate = run_gate([bad], baseline={}, use_roots=False)
        h.case("gate flips on seeded defect", rc_bad == 1,
               f"rc={rc_bad}, {len(gate)} gate finding(s)")
        fid = getattr(gate[0], "finding_id", "") if gate else ""
        rc_ok, _, _ = run_gate([bad], baseline={fid: "selftest"},
                               use_roots=False)
        h.case("baseline entry un-flips it", rc_ok == 0, f"rc={rc_ok}")
    return h.finish(
        f"SELFTEST OK: {len(FIXTURES)} defect classes detected, clean "
        "fixture lints clean, gate + baseline exit codes pinned",
        "SELFTEST FAIL: {failures} expectation(s) violated")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(ROOT, "paddle_tpu")],
                    help="files/dirs to sweep (default: the whole package)")
    ap.add_argument("--fail-on", choices=("error", "warning"),
                    default="error")
    ap.add_argument("--baseline", default=BASELINE_PATH)
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline file (show everything)")
    ap.add_argument("--inject", choices=sorted(FIXTURES), default=None,
                    help="lint one fixture seeded with a defect class")
    ap.add_argument("--selftest", action="store_true",
                    help="verify every defect class flips the gate")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current findings as baseline entries "
                         "with TODO justifications (then review them!)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="also print sub-gate findings")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.inject:
        import tempfile

        with tempfile.TemporaryDirectory(dir=ROOT) as tmp:
            bad = os.path.join(tmp, f"{args.inject}.py")
            with open(bad, "w") as f:
                f.write(FIXTURES[args.inject])
            rc, _, _ = run_gate([bad], fail_on=args.fail_on, baseline={},
                                use_roots=False)
        return rc

    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    rc, report, gate = run_gate(args.paths, fail_on=args.fail_on,
                                baseline=baseline, verbose=args.verbose)
    if args.write_baseline:
        entries = []
        for d in sorted(report.errors(),
                        key=lambda d: getattr(d, "finding_id", "")):
            fid = getattr(d, "finding_id", None)
            if fid:
                entries.append({
                    "id": fid,
                    "justification": baseline.get(
                        fid, "TODO: review and justify (or fix)"),
                })
        with open(args.baseline, "w") as f:
            json.dump({"_comment": [
                "Reviewed PT-RACE suppressions (docs/STATIC_ANALYSIS.md).",
                "Every entry needs a justification; stale entries are",
                "reported by the gate — remove them when the code is",
                "fixed."], "entries": entries}, f, indent=2)
            f.write("\n")
        print(f"baseline written: {args.baseline} ({len(entries)} entries)")
    return rc


if __name__ == "__main__":
    sys.exit(main())
