"""Elastic mesh-degrade serving (docs/RESILIENCE.md "Elastic serving mesh").

A tp-sharded engine that loses part of its device group raises the typed
:class:`MeshDegraded` signal (PT-SRV-008, ``device.loss`` fault site); the
elastic :class:`ServingSupervisor` harvests the column shards host-side,
rebuilds at the widest SURVIVING width that still divides both head
counts (or falls back to unsharded), re-splits the same bytes, and
replays every journaled request. Because the sharding contract is
column-parallel/all_gather-only, the reshard moves bytes — never values —
so greedy AND seeded streams stay bit-equal to an uninterrupted run.

These tests pin the full state machine (detect → reshard → re-admit →
verify), the control arms (``elastic=False``, a non-width-aware factory),
the MeshConfig validation edges, the PT-COMM degrade-width exemption, the
procfleet re-HELLO wire arm (PT-PROC-005 spawn validation included), and
the observability families. The compile-heavy tp=4→2 identity waves are
slow-marked; the fast in-process pin degrades mesh=2 → unsharded.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
from paddle_tpu.inference.recovery import ServingSupervisor
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          MeshConfig, MeshDegraded,
                                          PrefixCacheConfig, Request,
                                          SpecConfig)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

PRESETS = "paddle_tpu.inference.procfleet.presets"


@pytest.fixture(scope="module")
def model1():
    """4 heads / 2 kv heads: tp=2 is the widest buildable width, so one
    lost device leaves 1 survivor — the fall-to-unsharded arm."""
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model4():
    """4 kv heads: tp=4 is buildable AND tp=2 survives a 2-device loss."""
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, num_key_value_heads=4)
    return cfg, LlamaForCausalLM(cfg)


def _wave(cfg, seed=21):
    """Mixed greedy + seeded-sampled kwargs — byte-identity must survive
    the reshard in BOTH decode modes."""
    rng = np.random.default_rng(seed)
    pa = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    pc = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
    return [dict(prompt_ids=pa, max_new_tokens=6, seed=40),
            dict(prompt_ids=pb, max_new_tokens=8, temperature=0.9, seed=71,
                 top_k=5),
            dict(prompt_ids=pc, max_new_tokens=6, seed=52)]


def _builder(model, mesh_tp, **kw):
    """A width-aware engine factory: the elastic supervisor rebuilds
    through the ``mesh_tp`` parameter at the surviving width."""
    _, m = model

    def build(mesh_tp=mesh_tp):
        mesh = None if mesh_tp is None else MeshConfig(tp=int(mesh_tp))
        return ContinuousBatchingEngine(
            m, max_batch=4, max_len=64, page_size=8, block_size=4,
            prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8),
            mesh=mesh, **kw)

    return build


def _sup_serve(sup, wave, max_steps=800):
    reqs = [Request(**kw) for kw in wave]
    for r in reqs:
        sup.submit(r)
    sup.run_until_done(max_steps=max_steps)
    return [list(r.tokens) for r in reqs]


def _lose(arg, at=1, seed=5):
    """Lose ``arg`` devices on the second engine step (step 1 admits and
    prefills; at=1 lands the loss mid-decode)."""
    return FaultPlan(seed=seed, specs=[
        FaultSpec("device.loss", "lose", at=at, count=1, arg=arg)])


def _tp(engine):
    return (int(engine.mesh.tp)
            if getattr(engine, "mesh", None) is not None else 1)


# ---------------------------------------------------------------------------
# the fast in-process pin: mesh=2 loses 1 device -> fall to unsharded
# ---------------------------------------------------------------------------


def test_degrade_to_unsharded_fast_pin(model1, tmp_path):
    """tp=2 loses 1 device: the single survivor divides no width >= 2, so
    the supervisor falls back to unsharded — streams stay bit-equal to an
    uninterrupted run, the reshard counters tick, and the
    ``mesh_degrade`` span lands with ok=False (sharding lost entirely)."""
    from paddle_tpu.observability import TraceRecorder

    cfg, _ = model1
    wave = _wave(cfg)
    sup0 = ServingSupervisor(_builder(model1, None),
                             str(tmp_path / "ref.jrnl"))
    refs = _sup_serve(sup0, wave)
    sup0.close()

    tr = TraceRecorder()
    plan = _lose(1)
    sup = ServingSupervisor(_builder(model1, 2), str(tmp_path / "j.jrnl"),
                            tracer=tr)
    with plan:
        got = _sup_serve(sup, wave)
    sup.close()
    assert plan.fired().get("device.loss") == 1
    assert got == refs
    assert sup.stats["mesh_reshards"] == 1
    assert sup.stats["mesh_degraded"] == 1
    assert sup.stats["replayed_requests"] >= 1
    assert getattr(sup.engine, "mesh", None) is None   # fell to unsharded
    spans = [e for e in tr.events if e["name"] == "mesh_degrade"]
    assert len(spans) == 1
    args = spans[0]["args"]
    assert args["ok"] is False and args["old_tp"] == 2 \
        and args["new_tp"] == 1 and args["lost"] == 1


@pytest.mark.slow   # its own unsharded engine wave — the degrade pin above
#                     already proves unsharded engines rebuild; this arm only
#                     adds the no-mesh no-op assertion (tier-1 870s budget)
def test_unsharded_engine_ignores_device_loss(model1, tmp_path):
    """The ``device.loss`` hook is consulted on every step — but an
    unsharded engine has no device group to lose, so the event is inert
    (counters still advance: seeded plans stay aligned across arms)."""
    plan = _lose(2, at=0)
    sup = ServingSupervisor(_builder(model1, None),
                            str(tmp_path / "u.jrnl"))
    with plan:
        got = _sup_serve(sup, _wave(model1[0]))
    sup.close()
    assert plan.fired().get("device.loss") == 1
    assert sup.stats["mesh_reshards"] == 0
    assert all(got)


@pytest.mark.slow   # tp=2 engine wave; the exit-flipping control arm is also
#                     exercised every CI run by the mesh_device_loss drill
#                     (tools/fault_drill.py --no-recover, test_ci_gates pins)
def test_degrade_control_arms(model1, tmp_path):
    """``elastic=False`` lets the typed signal escape (the drill's
    control arm), and a factory with no ``mesh_tp`` parameter cannot
    serve the degrade — it escapes even with elastic on."""
    cfg, _ = model1
    wave = _wave(cfg)[:1]
    sup = ServingSupervisor(_builder(model1, 2), str(tmp_path / "c.jrnl"),
                            elastic=False)
    with _lose(1), pytest.raises(MeshDegraded) as ei:
        _sup_serve(sup, wave)
    sup.close()
    assert ei.value.lost == 1 and ei.value.survivors == 1
    assert "PT-SRV-008" in str(ei.value)

    width2 = _builder(model1, 2)

    def fixed_width():                 # no mesh_tp param, not width-aware
        return width2()

    sup2 = ServingSupervisor(fixed_width, str(tmp_path / "f.jrnl"))
    with _lose(1), pytest.raises(MeshDegraded):
        _sup_serve(sup2, wave)
    sup2.close()
    assert sup2.stats["mesh_reshards"] == 0


# ---------------------------------------------------------------------------
# MeshConfig validation edges (serving.py construction paths)
# ---------------------------------------------------------------------------


def test_mesh_config_validation_edges(model1):
    import jax

    _, m = model1

    def mk(**kw):
        return ContinuousBatchingEngine(
            m, max_batch=4, max_len=64, page_size=8, block_size=4,
            prefix_cache=PrefixCacheConfig(prefill_chunk=16,
                                           extra_blocks=8), **kw)

    # tp must divide BOTH head counts (4 heads / 2 kv heads -> tp=4 no)
    with pytest.raises(ValueError, match="divisible|divide"):
        mk(mesh=4)
    # an explicit device list shorter than tp is rejected at construction
    with pytest.raises(ValueError, match="needs 2 device"):
        mk(mesh=MeshConfig(tp=2, devices=jax.devices()[:1]))
    # int -> MeshConfig coercion
    e1 = mk(mesh=1)
    assert isinstance(e1.mesh, MeshConfig) and e1.mesh.tp == 1
    assert e1.mesh == MeshConfig(tp=1)
    # abstract=True: trace-only mesh, no real placement
    ea = mk(mesh=MeshConfig(tp=2, abstract=True))
    assert ea.mesh.abstract and ea._mesh is not None
    with pytest.raises(ValueError):
        MeshConfig(tp=0)


# ---------------------------------------------------------------------------
# PT-COMM: recorded degrade widths exempt the planned partial shrink
# ---------------------------------------------------------------------------


def test_comm_contract_degrade_width_exemption():
    from paddle_tpu.static.comm.checks import check_comm_contract
    from paddle_tpu.static.comm.manifest import CommManifest

    base = {"mesh": {"tp": 4}, "width": 4, "unsharded": False,
            "collectives": {"all_gather": 4}, "comm_bytes": 1000.0,
            "degrade_widths": [2]}
    # a still-sharded manifest at the recorded degrade width: count /
    # drift / bytes gates stay silent even where they would fire
    shrunk = CommManifest(program="mega_step@8,True", mesh={"tp": 2},
                          width=2, collective_eqns=6,
                          collectives={"all_gather": 6}, comm_bytes=1600.0)
    assert check_comm_contract(shrunk, base) == []
    # the same manifest at an UNRECORDED width gates as usual
    no_exempt = dict(base, degrade_widths=[])
    found = check_comm_contract(shrunk, no_exempt)
    assert found and any("drift" in f.finding_id for f in found)
    # losing sharding ENTIRELY is never exempt (PT-COMM-005 lost-sharding)
    flat = CommManifest(program="mega_step@8,True", unsharded=True)
    lost = check_comm_contract(flat, base)
    assert any("lost-sharding" in f.finding_id for f in lost)


def test_write_baseline_preserves_degrade_widths(tmp_path):
    """A baseline refresh must carry hand-recorded ``degrade_widths``
    forward — CommManifest.to_dict() cannot produce the field, so losing
    it on refresh would silently re-arm the gates on every degrade."""
    import json
    import sys

    sys.path.insert(0, "tools")
    try:
        from audit_collectives import load_baseline, write_baseline
    finally:
        sys.path.pop(0)
    from paddle_tpu.static.comm.manifest import CommManifest

    path = str(tmp_path / "baseline.json")
    man = CommManifest(program="mega_step@8,True", mesh={"tp": 2}, width=2,
                       collective_eqns=4, collectives={"all_gather": 4},
                       comm_bytes=100.0)
    write_baseline({man.program: man}, {}, path)
    doc = json.load(open(path))
    doc["programs"]["mega_step@8,True"]["degrade_widths"] = [1]
    json.dump(doc, open(path, "w"))
    write_baseline({man.program: man}, {}, path)      # the refresh
    merged, _ = load_baseline(path)
    assert merged["mega_step@8,True"]["degrade_widths"] == [1]


# ---------------------------------------------------------------------------
# observability: reshard counter + degraded gauge families
# ---------------------------------------------------------------------------


def test_mesh_degrade_metric_families(model1, tmp_path):
    from paddle_tpu.observability import supervisor_collector

    sup = ServingSupervisor(_builder(model1, None),
                            str(tmp_path / "m.jrnl"))
    sup.stats["mesh_reshards"] = 3
    sup.stats["mesh_degraded"] = 1
    fams = {f.name: f for f in supervisor_collector(sup)()}
    assert fams["pt_serving_mesh_reshards_total"].kind == "counter"
    assert fams["pt_serving_mesh_reshards_total"].samples[0][2] == 3.0
    assert fams["pt_serving_mesh_degraded"].kind == "gauge"
    assert fams["pt_serving_mesh_degraded"].samples[0][2] == 1.0
    # the raw stats keys must NOT double-export as pt_supervisor_*
    assert "pt_supervisor_mesh_reshards" not in fams
    assert "pt_supervisor_mesh_degraded" not in fams
    sup.close()


# ---------------------------------------------------------------------------
# procfleet: HELLO validation + the re-HELLO degrade piggyback
# ---------------------------------------------------------------------------


def test_proc_replica_hello_mesh_mismatch(tmp_path):
    """Regression: a worker whose engine width disagrees with
    ``WorkerSpec.mesh`` (preset/config skew via factory_kwargs) must die
    with a typed PT-PROC-005 at spawn, not serve at a width the router
    never asked for."""
    from paddle_tpu.inference.procfleet import (MeshMismatch, ProcReplica,
                                                WorkerSpec)

    spec = WorkerSpec(
        factory=f"{PRESETS}:tiny_llama_mesh_engine",
        journal_path=str(tmp_path / "w.jrnl"),
        factory_kwargs=dict(max_len=32, page_size=8, block_size=2, mesh=2),
        metrics_port=None)                 # spec.mesh is None -> wants tp=1
    with pytest.raises(MeshMismatch, match="PT-PROC-005"):
        ProcReplica(spec, idx=0, transport="loopback")


@pytest.mark.slow   # loopback mesh worker + rebuilt engine compile waves
def test_procfleet_mesh_degrade_rehello(tmp_path):
    """A loopback mesh=2 worker that loses a device absorbs the degrade
    in-process and piggybacks its new width on the next TOKENS reply (a
    re-HELLO without a reconnect): the proxy re-weights capacity, the
    router keeps routing to the SAME replica — mesh-degrade is distinct
    from replica death, no failover churn."""
    from paddle_tpu.inference.procfleet import (ProcFleetConfig,
                                                ProcFleetRouter)

    cfg = ProcFleetConfig(
        factory=f"{PRESETS}:tiny_llama_mesh_engine",
        factory_kwargs=dict(max_len=64, page_size=8, block_size=4),
        transport="loopback", mesh=2)
    fleet = ProcFleetRouter(cfg, str(tmp_path), num_replicas=1)
    try:
        rep = fleet.replicas[0].sup
        assert rep.engine.mesh_tp == 2
        assert rep.capacity_weight() == pytest.approx(1.0)
        tiny = LlamaConfig.tiny()
        rng = np.random.default_rng(33)
        prompts = [rng.integers(0, tiny.vocab_size, (n,)).astype(np.int32)
                   for n in (8, 6, 10)]
        plan = _lose(1, seed=7)
        reqs = [Request(p, max_new_tokens=6) for p in prompts]
        with plan:
            for r in reqs:
                fleet.submit(r)
            fleet.run_until_done()
        assert plan.fired().get("device.loss") == 1
        assert all(r.done and not r.failed for r in reqs)
        # the piggybacked width landed on the proxy, same replica object
        assert fleet.replicas[0].sup is rep
        assert rep.engine.mesh_tp == 1
        assert rep.capacity_weight() == pytest.approx(0.5)
        assert fleet.stats.get("proc_mesh_degrades", 0) >= 1
        assert fleet.stats.get("replica_deaths", 0) == 0
        # the degraded replica still serves
        more = [Request(p, max_new_tokens=4) for p in prompts[:2]]
        for r in more:
            fleet.submit(r)
        fleet.run_until_done()
        assert all(r.done and not r.failed for r in more)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# slow identity waves: tp=4 -> tp=2 (plain, spec decode, int8 KV)
# ---------------------------------------------------------------------------


def _degrade_identity(model, tmp_path, tag, wave=None, **engine_kw):
    """Shared 4->2 harness: refs from an uninterrupted tp=4 supervisor,
    then the same wave through a 2-device loss — streams must match
    bit-for-bit and the engine must land at tp=2."""
    cfg, _ = model
    wave = _wave(cfg) if wave is None else wave
    # a repeated prompt rides the radix prefix cache / COW admission path
    wave.append(dict(prompt_ids=wave[0]["prompt_ids"], max_new_tokens=4))
    build = _builder(model, 4, **engine_kw)
    sup0 = ServingSupervisor(build, str(tmp_path / f"{tag}-ref.jrnl"))
    refs = _sup_serve(sup0, wave)
    sup0.close()
    plan = _lose(2)
    sup = ServingSupervisor(build, str(tmp_path / f"{tag}.jrnl"))
    with plan:
        got = _sup_serve(sup, wave)
    assert plan.fired().get("device.loss") == 1
    assert got == refs
    assert sup.stats["mesh_reshards"] == 1
    assert _tp(sup.engine) == 2
    return sup


@pytest.mark.slow   # tp=4 + rebuilt tp=2 compile waves
def test_degrade_4to2_identity(model4, tmp_path):
    sup = _degrade_identity(model4, tmp_path, "plain")
    # the rebuilt engine re-recorded its census under the NEW static key
    assert any(k.startswith("mega_step") for k in sup.engine._mesh_programs)
    sup.close()


@pytest.mark.slow   # spec engines at two widths = their own compile waves
def test_degrade_spec_decode_identity(model4, tmp_path):
    # greedy-only wave: a batch with sampling rows keeps the scan
    # (non-spec) mega-step, so the drafter would never engage post-shrink
    cfg, _ = model4
    wave = [dict(kw) for kw in _wave(cfg)]
    for kw in wave:
        kw.pop("temperature", None)
        kw.pop("top_k", None)
    sup = _degrade_identity(model4, tmp_path, "spec", wave=wave,
                            speculative=SpecConfig(k=3))
    assert sup.engine.stats["spec_steps"] > 0     # drafter active post-shrink
    assert "spec_verify" in sup.engine._mesh_programs
    sup.close()


@pytest.mark.slow   # int8 engines at two widths = their own compile waves
def test_degrade_int8_kv_identity(model4, tmp_path):
    """int8 KV pools shard along the kv-head axis — the per-(page, head)
    scales ride the same reshard, so the quantized arm stays bit-equal."""
    sup = _degrade_identity(model4, tmp_path, "int8", kv_cache="int8")
    sup.close()
