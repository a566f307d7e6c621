"""Mesh-sharded serving (docs/SERVING.md "Sharded serving").

The tp-sharded engine holds one identity CONTRACT: every tp-sharded
weight splits along its OUTPUT dimension (column-parallel), so the only
collectives are all_gathers of disjoint shards and every device computes
byte-identical values — greedy AND seeded-sampled streams at mesh=N must
equal the 1-device engine's bit-for-bit. These tests pin that
contract over a REAL 2-wide CPU device mesh (tests/conftest.py forces
``--xla_force_host_platform_device_count=8``), the abstract-mesh trace
path the PT-COMM/PT-COST gates audit through, the procfleet per-worker
device groups, and the mesh observability families.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          MeshConfig, PrefixCacheConfig,
                                          Request, SpecConfig)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    return cfg, LlamaForCausalLM(cfg)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)


def _wave(cfg):
    """Mixed greedy + seeded-sampled requests with ragged lengths — the
    identity claim must hold across BOTH decode modes and chunk buckets."""
    prompts = [_prompt(cfg, n, 300 + n) for n in (5, 16, 9, 16, 40, 3)]
    kws = [dict(max_new_tokens=6), dict(max_new_tokens=4),
           dict(max_new_tokens=8, temperature=0.8, seed=7, top_k=5),
           dict(max_new_tokens=4, temperature=1.1, seed=3, top_p=0.9),
           dict(max_new_tokens=6), dict(max_new_tokens=8)]
    return prompts, kws


def _serve(eng, prompts, kws, stagger=True):
    reqs = [Request(p, **k) for p, k in zip(prompts, kws)]
    head = reqs[:3] if stagger else reqs
    for r in head:
        eng.add_request(r)
    if stagger:
        eng.step()
        eng.step()
        for r in reqs[3:]:
            eng.add_request(r)
    eng.run_until_done(max_steps=500)
    return [list(r.tokens) for r in reqs]


def _mk(model, mesh=None, max_batch=8, **kw):
    _, m = model
    return ContinuousBatchingEngine(
        m, max_batch=max_batch, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8),
        mesh=mesh, **kw)


@pytest.fixture(scope="module")
def one_device_tokens(model):
    """The 1-device streams every mesh arm must reproduce."""
    cfg, _ = model
    prompts, kws = _wave(cfg)
    return _serve(_mk(model), prompts, kws)


def test_mesh_identity_greedy_and_sampled(model, one_device_tokens):
    """mesh=1 and mesh=2 greedy/seeded streams are bit-equal to the
    1-device engine's, the mesh counters tick, and the pt_serving_*
    collector families render on sharded AND unsharded engines (they are
    REQUIRED in tools/scrape_metrics.py — they must never vanish)."""
    from paddle_tpu.observability import engine_collector

    cfg, _ = model
    prompts, kws = _wave(cfg)
    assert _serve(_mk(model, mesh=1), prompts, kws) == one_device_tokens
    e2 = _mk(model, mesh=2)
    assert _serve(e2, prompts, kws) == one_device_tokens
    assert e2.stats["mesh_decode_steps"] > 0
    assert e2.stats["mesh_collective_bytes"] > 0
    # the first-dispatch census recorded per program variant
    assert any(k.startswith("mega_step") for k in e2._mesh_programs)
    assert any(k.startswith("prefill_chunk") for k in e2._mesh_programs)
    fams = {f.name: f for f in engine_collector(e2)()}
    assert fams["pt_serving_mesh_shape"].samples[0][2] == 2.0
    assert fams["pt_serving_collective_bytes_total"].samples[0][2] > 0
    assert fams["pt_serving_mesh_decode_steps_total"].samples[0][2] > 0
    fams0 = {f.name: f for f in engine_collector(_mk(model))()}
    assert fams0["pt_serving_mesh_shape"].samples[0][2] == 1.0
    assert fams0["pt_serving_collective_bytes_total"].samples[0][2] == 0.0


def test_mesh_config_equivalent_to_int(model):
    """``mesh=2`` and ``mesh=MeshConfig(tp=2)`` build the same engine
    (structural pin — the served identity rides the int arm above)."""
    e = _mk(model, mesh=MeshConfig(tp=2))
    ei = _mk(model, mesh=2)
    assert e.mesh.tp == ei.mesh.tp == 2
    assert e.mesh == ei.mesh


@pytest.mark.slow   # second sharded spec engine = its own compile wave
def test_mesh_spec_identity(model):
    """The K+1-wide spec-verify path at mesh=2: greedy streams bit-equal
    to the unsharded spec engine AND the non-spec engine (spec decode is
    output-invariant), with the drafter actually proposing."""
    cfg, _ = model
    prompts = [_prompt(cfg, n, 40 + n) for n in (5, 16, 9, 3)]
    kws = [dict(max_new_tokens=8), dict(max_new_tokens=6),
           dict(max_new_tokens=8), dict(max_new_tokens=10)]
    want = _serve(_mk(model), prompts, kws, stagger=False)
    sp = _mk(model, mesh=2, speculative=SpecConfig(k=3))
    got = _serve(sp, prompts, kws, stagger=False)
    assert got == want
    assert sp.stats["spec_steps"] > 0
    assert "spec_verify" in sp._mesh_programs


@pytest.mark.slow   # two fresh int8 engines = two compile waves
def test_mesh_int8_kv_identity(model):
    """int8 paged KV pools shard along the kv-head axis like the bf16
    pools (one spec prefix covers pools AND per-page scales)."""
    cfg, _ = model
    prompts, kws = _wave(cfg)
    want = _serve(_mk(model, kv_cache="int8"), prompts, kws)
    assert _serve(_mk(model, kv_cache="int8", mesh=2), prompts, kws) == want


@pytest.mark.slow   # fresh 1-layer tied model, two more compile waves
def test_mesh_tied_embeddings_identity():
    """Tied embeddings keep the lm head replicated — no logits gather —
    and the identity contract still holds."""
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, tie_word_embeddings=True)
    m = LlamaForCausalLM(cfg)
    model = (cfg, m)
    prompts = [_prompt(cfg, n, 80 + n) for n in (5, 9, 3)]
    kws = [dict(max_new_tokens=6), dict(max_new_tokens=4),
           dict(max_new_tokens=8, temperature=0.9, seed=5, top_k=4)]
    want = _serve(_mk(model, max_batch=4), prompts, kws, stagger=False)
    got = _serve(_mk(model, max_batch=4, mesh=2), prompts, kws,
                 stagger=False)
    assert got == want


def test_mesh_validation(model):
    """The mesh contract is validated at construction, not discovered as
    a shape error three programs deep."""
    _, m = model
    with pytest.raises(ValueError, match="prefix"):
        ContinuousBatchingEngine(m, max_batch=4, max_len=64, page_size=8,
                                 mesh=2)
    with pytest.raises(ValueError, match="divisible|divide"):
        _mk(model, mesh=3)         # 4 heads / 2 kv heads: tp=3 can't split
    with pytest.raises(ValueError):
        MeshConfig(tp=0)
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(5)
    g = GPTForCausalLM(GPTConfig.tiny(num_hidden_layers=1))
    with pytest.raises(ValueError, match="tp_serving"):
        ContinuousBatchingEngine(
            g, max_batch=4, max_len=64, page_size=8,
            prefix_cache=PrefixCacheConfig(), mesh=2)


def test_abstract_mesh_trace_all_gather_only(model):
    """The PT-COMM/PT-COST audit path: an ABSTRACT tp mesh traces the
    sharded programs with no devices and no placement, and the census is
    all_gather-only — the column-parallel contract that makes mesh=N
    byte-identical (a psum here would break bit-equality)."""
    import jax

    from paddle_tpu.static.comm.collectives import iter_collectives

    _, m = model
    eng = ContinuousBatchingEngine(
        m, max_batch=8, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8),
        speculative=SpecConfig(k=3), mesh=MeshConfig(tp=2, abstract=True))
    disp = eng._build_mega_jit()
    seeds, temps, tops, topks = eng._dev_samp
    jaxpr = jax.make_jaxpr(
        lambda *a: disp(*a, n_steps=2, do_sample=True))(
        eng._params, eng._last_tok, eng.caches["kv"], eng.caches["tables"],
        eng._dev_pos, eng._dev_act, seeds, temps, tops, topks)
    mega = list(iter_collectives(jaxpr))
    assert mega and all(c.prim == "all_gather" for c in mega)
    sdisp = eng._build_spec_jit()
    caps = np.zeros(eng.max_batch, np.int32)
    j2 = jax.make_jaxpr(lambda *a: sdisp(*a))(
        eng._params, eng._last_tok, eng.caches["kv"], eng.caches["tables"],
        eng._dev_pos, eng._dev_act, eng._dev_hist, eng._dev_hlen, caps)
    spec = list(iter_collectives(j2))
    assert spec and all(c.prim == "all_gather" for c in spec)
    # dispatching through the cached program recorded its census
    assert eng._mesh_programs.get("mega_step@2,True", 0) > 0


def test_reshard_trace_span(model):
    """Placing weights + KV pools on the mesh emits a ``reshard`` span —
    the boundary a profiler needs to separate placement cost from
    decode cost."""
    from paddle_tpu.observability import TraceRecorder

    _, m = model
    tr = TraceRecorder()
    ContinuousBatchingEngine(
        m, max_batch=4, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8),
        mesh=2, tracer=tr)
    assert "reshard" in {e["name"] for e in tr.events}


@pytest.mark.slow   # one extra 4-wide compile wave beside the module arms
def test_mesh4_identity(model):
    """The widest split the tiny config admits per-head is tp=2 (2 kv
    heads) — so mesh=4 must be REJECTED, and a 4-kv-head config must
    serve bit-identically at tp=4."""
    with pytest.raises(ValueError, match="divisible|divide"):
        _mk(model, mesh=4)
    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, num_key_value_heads=4)
    m = LlamaForCausalLM(cfg)
    model4 = (cfg, m)
    prompts = [_prompt(cfg, n, 60 + n) for n in (5, 9, 3)]
    kws = [dict(max_new_tokens=6), dict(max_new_tokens=4),
           dict(max_new_tokens=8, temperature=0.8, seed=7, top_k=5)]
    want = _serve(_mk(model4, max_batch=4), prompts, kws, stagger=False)
    got = _serve(_mk(model4, max_batch=4, mesh=4), prompts, kws,
                 stagger=False)
    assert got == want


# ---------------------------------------------------------------------------
# procfleet: per-worker device groups
# ---------------------------------------------------------------------------

PRESETS = "paddle_tpu.inference.procfleet.presets"


@pytest.mark.slow   # four engine processes' worth of compiles (2 fleets)
def test_fleet_mesh_device_groups(tmp_path):
    """A loopback procfleet at mesh=2: each replica's engine serves over
    its own DISJOINT 2-device group, the HELLO carries ``mesh_tp``, and
    the streams are bit-equal to the unsharded fleet."""
    from paddle_tpu.inference.procfleet import (ProcFleetConfig,
                                                ProcFleetRouter)

    prompts = [_prompt(LlamaConfig.tiny(), n, 40 + n) for n in (5, 9, 12, 3)]

    def serve(mesh, sub):
        cfg = ProcFleetConfig(
            factory=f"{PRESETS}:tiny_llama_mesh_engine",
            factory_kwargs=dict(max_len=64, page_size=8, block_size=4),
            transport="loopback", mesh=mesh)
        fleet = ProcFleetRouter(cfg, str(tmp_path / sub), num_replicas=2)
        try:
            reqs = [Request(p, max_new_tokens=6) for p in prompts]
            for r in reqs:
                fleet.submit(r)
            fleet.run_until_done()
            tp = [fleet.replicas[i].sup.engine.mesh_tp for i in range(2)]
            return [list(r.tokens) for r in reqs], tp
        finally:
            fleet.close()

    want, tp0 = serve(None, "flat")
    got, tp2 = serve(2, "mesh")
    assert tp0 == [1, 1] and tp2 == [2, 2]
    assert got == want
