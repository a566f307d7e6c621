"""Observability subsystem (paddle_tpu/observability — docs/OBSERVABILITY.md).

Covers the metrics registry (typed instruments, Prometheus text render +
parse roundtrip, histogram quantiles, collector isolation), the HTTP
MetricsServer, the TraceRecorder's lifecycle semantics (exactly one
terminal per submitted request, hwm-deduped token accounting, recovered
tagging, Perfetto-loadable chrome-trace schema), and the integration
through a real engine wave + a supervisor crash-replay.

The end-to-end HTTP + fleet path is CI-gated separately via
``tools/scrape_metrics.py --selftest`` (tests/test_ci_gates.py).
"""

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (Counter, Histogram, MetricFamily,
                                      MetricsRegistry, MetricsServer,
                                      TraceRecorder, engine_collector,
                                      parse_prometheus_text)
from paddle_tpu.inference.serving import (ContinuousBatchingEngine, Request,
                                          RequestShed)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

TERMINALS = ("finish", "evict", "shed", "fail")


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


# ---------------------------------------------------------------------------
# metrics registry (host-only)
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_render_parse_roundtrip_with_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("pt_t_total", "things")
        c.inc(2, kind="a")
        c.inc(kind='b "quoted"\nline')
        g = reg.gauge("pt_level")
        g.set(1.5)
        fams = parse_prometheus_text(reg.dump())
        assert fams["pt_t_total"].kind == "counter"
        vals = {tuple(sorted(lbl.items())): v
                for _, lbl, v in fams["pt_t_total"].samples}
        assert vals[(("kind", "a"),)] == 2
        assert vals[(("kind", 'b "quoted"\nline'),)] == 1
        assert fams["pt_level"].samples[0][2] == 1.5

    def test_histogram_buckets_quantile_and_exposition(self):
        reg = MetricsRegistry()
        h = reg.histogram("pt_lat_ms", buckets=(1, 10, 100))
        for v in (0.5, 5, 5, 50, 5000):
            h.observe(v)
        assert h.count() == 5
        # q50 lands in the (1,10] bucket; past-the-end clamps to last bound
        assert 1 <= h.quantile(0.5) <= 10
        assert h.quantile(0.999) == 100
        fams = parse_prometheus_text(reg.dump())
        s = fams["pt_lat_ms"].samples
        inf = [v for suf, lbl, v in s
               if suf == "_bucket" and lbl.get("le") == "+Inf"]
        assert inf == [5]
        assert any(suf == "_sum" and abs(v - 5060.5) < 1e-6
                   for suf, _, v in s)

    def test_instrument_identity_and_type_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("pt_x") is reg.counter("pt_x")
        with pytest.raises(ValueError):
            reg.gauge("pt_x")
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            Counter("pt_ok").inc(lab_el_bad="x", **{"0bad": "y"})

    def test_counter_never_decrements(self):
        with pytest.raises(ValueError):
            Counter("pt_c").inc(-1)

    def test_same_name_families_merge_and_collector_errors_isolated(self):
        reg = MetricsRegistry()
        reg.register_collector(
            lambda: [MetricFamily("pt_dup", "gauge").add(1, replica="0")])
        reg.register_collector(
            lambda: [MetricFamily("pt_dup", "gauge").add(2, replica="1")])
        reg.register_collector(lambda: 1 / 0)    # must not kill the scrape
        text = reg.dump()
        assert text.count("# TYPE pt_dup gauge") == 1   # ONE family block
        fams = parse_prometheus_text(text)
        assert len(fams["pt_dup"].samples) == 2
        assert fams["pt_collector_errors"].samples[0][2] == 1

    def test_http_server_scrape_and_healthz(self):
        reg = MetricsRegistry()
        reg.counter("pt_up_total").inc()
        srv = MetricsServer(reg, port=0)     # port-0: ephemeral, test-safe
        try:
            body = urllib.request.urlopen(srv.url, timeout=5).read()
            assert b"pt_up_total 1" in body
            hz = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5).read()
            assert hz == b"ok"
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# trace recorder semantics (host-only)
# ---------------------------------------------------------------------------

class TestTraceRecorder:
    def test_lifecycle_and_chrome_schema(self):
        tr = TraceRecorder()
        tr.submit(1, 10, 8)
        tr.admit(1, 0.002, hit_tokens=4, miss_tokens=6)
        tr.prefill_chunk(1, tr.now(), 16)
        tr.first_token(1)
        tr.finish(1, 8)
        tr.submit(2, 4, 4)
        assert tr.incomplete() == [2]
        tr.shed(2)
        assert tr.incomplete() == []
        assert tr.lifecycle(1) == ["submit", "admit", "prefill_chunk",
                                   "first_token", "finish"]
        doc = tr.export_chrome()
        assert isinstance(doc["traceEvents"], list)
        for e in doc["traceEvents"]:
            assert {"name", "ph", "ts"} <= set(e)
            if e["ph"] == "X":
                assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0

    def test_hwm_dedup_and_recovered_tagging(self):
        tr = TraceRecorder()
        tr.submit(7, 4, 8)
        tr.first_token(7)
        tr.tokens(7, 3)
        base = tr._c_tokens.value()
        tr.mark_recovered(7, hwm=3)
        tr.tokens(7, 2)                  # catch-up below the mark: nothing
        assert tr._c_tokens.value() == base
        tr.tokens(7, 5)                  # past the mark: +2
        assert tr._c_tokens.value() == base + 2
        tr.first_token(7)                # replay: TTFT not reset
        tr.finish(7, 8)
        names = tr.lifecycle(7)
        assert "first_token_replay" in names and "recovered" in names
        post = [e for e in tr.events if e.get("tid") == 7][-1]
        assert post["args"].get("recovered") is True
        # tokens counter ends at the true stream length, not hwm + replay
        assert tr._c_tokens.value() == base + 5

    def test_resubmit_reopens_terminal_and_slo_rates(self):
        tr = TraceRecorder()
        tr.submit(3, 4, 4)
        tr.shed(3)
        tr.submit(3, 4, 4)               # fleet fell through to a replica
        assert tr.incomplete() == [3]    # reopened, needs a terminal again
        tr.first_token(3)
        tr.finish(3, 4)
        assert tr.incomplete() == []
        assert tr.resubmits == 1
        slo = tr.slo_summary()
        assert slo["submitted"] == 1     # one request, not two
        assert slo["p50_time_to_first_token_ms"] is not None

    def test_event_buffer_bounded(self):
        tr = TraceRecorder(max_events=5)
        for i in range(10):
            tr.instant("tick", rid=1)
        assert len(tr.events) == 5 and tr.dropped == 5
        assert tr.export_chrome()["otherData"]["dropped_events"] == 5

    def test_concurrent_stamping_is_exact(self):
        """PT-RACE-001 regression (tools/lint_concurrency.py): ONE recorder
        is shared by every replica of a fleet, and under
        ``FleetConfig(parallel_step=True)`` the stamp sites run on
        concurrent replica threads while the driver reads exports. The
        recorder lock must keep the bookkeeping exact: no lost events, no
        lost streamed-token increments, one terminal per rid — unlocked
        dict/list mutation loses updates under this exact load."""
        import threading

        tr = TraceRecorder(max_events=500_000)
        n_threads, n_reqs, n_toks = 8, 25, 20
        errs = []

        def replica(t):
            try:
                for i in range(n_reqs):
                    rid = t * 1000 + i
                    tr.submit(rid, 4, n_toks, tags={"replica": t})
                    tr.first_token(rid, tags={"replica": t})
                    for k in range(1, n_toks + 1):
                        tr.tokens(rid, k)
                    tr.finish(rid, n_toks, tags={"replica": t})
                    tr.export_chrome()        # driver-side read races in
                    tr.incomplete()
            except Exception as e:            # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=replica, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        total = n_threads * n_reqs
        slo = tr.slo_summary()
        assert slo["submitted"] == total
        assert slo["tokens_streamed"] == total * n_toks
        assert tr.incomplete() == []
        reg = tr.registry
        assert reg.get("pt_serving_requests_terminal_total") \
                  .value(kind="finish") == total
        # every lane carries exactly one terminal and the full chain
        doc = tr.export_chrome()
        assert len([e for e in doc["traceEvents"]
                    if e["name"] == "finish"]) == total


# ---------------------------------------------------------------------------
# engine / supervisor integration. Tier-1 wall clock is at its 870 s
# ceiling (see memory / PR 5's budget rescue), so the FAST pin is a
# minimal chain test on the engine without a prefix cache; the full
# supervisor lifecycle + crash-replay proof is slow-marked (its span
# semantics are all unit-pinned fast above, and tools/scrape_metrics.py
# --selftest gates the end-to-end fleet path).
# ---------------------------------------------------------------------------

def test_traced_minimal_chain_fast(model):
    """Fast integration pin: one request through the engine without a
    prefix cache (three compiled programs) produces the ordered
    submit->admit->first_token->finish chain, exactly one terminal, a
    schema-valid chrome export, and a TTFT observation."""
    cfg, m = model
    tr = TraceRecorder()
    eng = ContinuousBatchingEngine(m, max_batch=1, max_len=16, page_size=8,
                                   block_size=2, tracer=tr)
    req = Request(_prompt(cfg, 4, 3), max_new_tokens=2)
    eng.add_request(req)
    eng.run_until_done(max_steps=50)
    assert req.done and not req.failed
    assert tr.incomplete() == []
    names = tr.lifecycle(req.rid)
    assert [n for n in names if n in TERMINALS] == ["finish"]
    chain = iter(names)
    assert all(s in chain for s in ("submit", "admit", "first_token",
                                    "finish"))
    doc = json.loads(json.dumps(tr.export_chrome()))
    assert doc["traceEvents"] and all(
        {"name", "ph", "ts"} <= set(e) for e in doc["traceEvents"])
    assert tr.slo_summary()["p50_time_to_first_token_ms"] is not None


@pytest.mark.slow   # supervisor + crash rebuild = two engine-compile sets;
#                     every span semantic asserted here has a fast host-only
#                     pin in TestTraceRecorder, and the e2e fleet path is
#                     gated by tools/scrape_metrics.py --selftest
def test_traced_serving_lifecycle_and_crash_replay(model, tmp_path):
    """End-to-end trace contract over a supervisor-wrapped engine:

    wave 1 — every submitted request ends in exactly ONE terminal span
    (finish / evict for a blown deadline / shed for an infeasible one),
    the served chain is submit->admit->first_token->finish in order, the
    chrome export is Perfetto-loadable JSON, the SLO summary computes
    TTFT percentiles from the histograms, and the scrape surface carries
    the engine/pool/SLO families.

    wave 2 (crash mid-wave) — spans across the crash-replay carry
    recovered=true, the replayed first token does not reset TTFT
    (``first_token_replay``), streamed-token accounting is deduped
    against the journal hwm (the counter ends at the true stream
    length), and each request still reaches exactly one terminal."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.recovery import ServingSupervisor
    from paddle_tpu.observability import supervisor_collector

    cfg, m = model
    reg = MetricsRegistry()
    tr = TraceRecorder(registry=reg)

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2,
                                        prefix_cache=True)

    sup = ServingSupervisor(build, str(tmp_path / "j.jrnl"), tracer=tr)
    reg.register_collector(supervisor_collector(sup))

    # -- wave 1: served + deadline-evicted + feasibility-shed ------------
    served = [Request(_prompt(cfg, 8, 1 + i), max_new_tokens=4, seed=1 + i)
              for i in range(3)]
    for r in served:
        sup.submit(r)
    # a queued request whose deadline expires before admission -> evict
    doomed = Request(_prompt(cfg, 8, 9), max_new_tokens=4, deadline_s=1e-6)
    sup.submit(doomed)
    sup.run_until_done(max_steps=500)
    assert all(r.done and not r.failed for r in served)
    assert doomed.failed and "deadline" in doomed.error
    assert sup.engine._ema_tok_s is not None   # rate measured -> shed arms
    with pytest.raises(RequestShed):
        sup.submit(Request(_prompt(cfg, 8, 10), max_new_tokens=4,
                           deadline_s=1e-9))
    assert tr.incomplete() == []
    kinds = {}
    for rid in [r.rid for r in served] + [doomed.rid]:
        names = tr.lifecycle(rid)
        terms = [n for n in names if n in TERMINALS]
        assert len(terms) == 1, (rid, names)
        kinds[rid] = terms[0]
    assert all(kinds[r.rid] == "finish" for r in served)
    assert kinds[doomed.rid] == "evict"
    assert any(st == "shed" for st in tr._state.values())
    chain = iter(tr.lifecycle(served[0].rid))
    assert all(step in chain for step in
               ("submit", "admit", "first_token", "finish"))
    # chrome trace: valid JSON document with schema'd events
    doc = json.loads(json.dumps(tr.export_chrome()))
    assert doc["traceEvents"] and all(
        {"name", "ph", "ts"} <= set(e) for e in doc["traceEvents"])
    slo = tr.slo_summary()
    assert slo["p50_time_to_first_token_ms"] is not None
    assert slo["p99_time_to_first_token_ms"] >= slo[
        "p50_time_to_first_token_ms"]
    assert slo["shed_rate"] > 0
    text = reg.dump()
    for fam in ("pt_engine_queue_depth", "pt_pool_free_blocks",
                "pt_supervisor_recoveries",
                "pt_serving_time_to_first_token_ms_bucket"):
        assert fam in text, fam

    # -- wave 2: crash mid-wave, spans survive the replay ----------------
    reqs = [Request(_prompt(cfg, 8, 50 + i), max_new_tokens=6, seed=50 + i)
            for i in range(2)]
    for r in reqs:
        sup.submit(r)
    plan = FaultPlan(seed=7, specs=[
        FaultSpec("serving.step", "kill", at=1, count=1)])
    with plan:
        sup.run_until_done(max_steps=2000)
    assert sup.recoveries == 1
    assert all(r.done and not r.failed for r in reqs)
    sup.close()
    assert tr.incomplete() == []
    for r in reqs:
        names = tr.lifecycle(r.rid)
        assert sum(1 for n in names if n in TERMINALS) == 1, names
        assert "recovered" in names and "first_token_replay" in names
        evs = [e for e in tr.events if e.get("tid") == r.rid]
        # everything after the crash is tagged; the terminal included
        assert evs[-1]["args"].get("recovered") is True
        # dedup: the twin re-generated the delivered prefix, but streamed
        # accounting ends exactly at the caller's stream length
        assert tr._streamed[r.rid] == len(r.output)
    rec = [e for e in tr.events if e["name"] == "recovery"]
    assert rec and rec[0]["args"]["code"] == "PT-SRV-001"
    # the post-rebuild engine is what the collector now scrapes
    assert "pt_supervisor_recoveries 1" in reg.dump()
