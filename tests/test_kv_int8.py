"""int8 paged-KV block format (ops/paged_attention.QuantizedKVPool,
engine opt-in via ``KVCacheConfig(dtype="int8")`` — docs/SERVING.md
"int8 KV cache").

The contract under test: per-(page, head) absmax scales beside the pool,
quantize-on-append (scatter-max scale growth + bounded requantization),
dequantize-in-gather, COW copying scales with page bytes, and the PTKV1
migration artifact carrying dtype + scales with crc over the int8 bytes.
Engine waves are slow-marked (tier-1 budget); the FAST pins below cover
the quant math, append/requant error bounds and the chain round trip with
no model or compile.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.quantization import (KV_QMAX, QuantizedKVPool,
                                     dequantize_kv, kv_absmax, quantize_kv)
from paddle_tpu.ops.paged_attention import (append_paged_kv, copy_pages,
                                            gather_chain_pages,
                                            gather_chain_scales,
                                            gather_paged_kv,
                                            paged_decode_attention,
                                            paged_prefill_attention,
                                            paged_verify_attention,
                                            scatter_chain_pages)


def _pool(P=4, h=2, page=8, d=4):
    return QuantizedKVPool(jnp.zeros((P, h, page, d), jnp.int8),
                           jnp.zeros((P, h), jnp.float32))


# ---------------------------------------------------------------------------
# FAST pins: quant math + append/requant bounds (no model, no compile)
# ---------------------------------------------------------------------------

def test_quantize_dequantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = rng.normal(0, 3.0, (16, 4)).astype(np.float32)
    scale = np.abs(x).max(axis=-1, keepdims=True)        # per-row absmax
    q = np.asarray(quantize_kv(x, scale))
    assert q.dtype == np.int8
    back = np.asarray(dequantize_kv(q, scale))
    # one quantization event: error <= step/2 = scale / (2 * KV_QMAX)
    assert np.all(np.abs(back - x) <= scale / (2 * KV_QMAX) + 1e-7)
    # zero-scale blocks hold zeros and dequantize to zeros
    z = np.asarray(quantize_kv(np.zeros((2, 4), np.float32),
                               np.zeros((2, 1), np.float32)))
    assert not z.any()
    assert kv_absmax(x[:, None, :]).shape == (16, 1)


def test_append_quantizes_and_requants_on_scale_growth():
    pool = _pool()
    tables = np.array([[0, 1], [2, 3]], np.int32)
    rng = np.random.default_rng(1)
    # first append: small values at position 0 of each row
    small = rng.normal(0, 0.5, (2, 2, 4)).astype(np.float32)
    k1, _ = append_paged_kv(pool, _pool(), small, small, tables,
                            np.array([0, 0], np.int32))
    s1 = np.asarray(k1.scale)
    assert np.allclose(s1[[0, 2]], np.abs(small).max(-1), atol=1e-6)
    assert not s1[[1, 3]].any()                 # untouched blocks stay 0
    # second append: 10x larger values at position 1 -> scale grows and
    # the stored position-0 values are requantized under the new scale
    big = (10.0 * small).astype(np.float32)
    k2, _ = append_paged_kv(k1, _pool(), big, big, tables,
                            np.array([1, 1], np.int32))
    s2 = np.asarray(k2.scale)
    assert np.all(s2[[0, 2]] >= s1[[0, 2]])
    dense = np.asarray(dequantize_kv(
        k2.data, np.asarray(k2.scale)[:, :, None, None]))
    # both generations of content bounded by the FINAL step size (requant
    # double-rounding costs at most one extra step)
    step = s2[[0, 2]][..., None] / KV_QMAX      # [2, h, 1]
    err0 = np.abs(dense[[0, 2]][:, :, 0, :] - small)
    err1 = np.abs(dense[[0, 2]][:, :, 1, :] - big)
    assert np.all(err0 <= 1.5 * step + 1e-7)
    assert np.all(err1 <= 0.5 * step + 1e-7)


def test_unchanged_blocks_are_byte_stable_across_appends():
    """Appends that do not grow a block's scale must leave every OTHER
    block's int8 bytes bit-identical (ratio 1.0 requant is exact)."""
    pool = _pool()
    tables = np.array([[0, 1]], np.int32)
    rng = np.random.default_rng(2)
    x = rng.normal(0, 1.0, (1, 2, 4)).astype(np.float32)
    k1, _ = append_paged_kv(pool, _pool(), x, x, tables,
                            np.array([0], np.int32))
    before = np.asarray(k1.data[0]).copy()
    # append a SMALLER token at position 1 — block 0's scale is unchanged
    k2, _ = append_paged_kv(k1, _pool(), (0.1 * x).astype(np.float32),
                            (0.1 * x).astype(np.float32), tables,
                            np.array([1], np.int32))
    after = np.asarray(k2.data[0])
    assert np.array_equal(before[:, 0, :], after[:, 0, :])


def test_attention_reads_dequantize_and_match_fp_within_bound():
    """Decode / prefill / verify attention over an int8 pool match the
    same attention over the fp pool within the quantization error."""
    rng = np.random.default_rng(3)
    P, h, page, d, b = 4, 2, 8, 4, 2
    tables = np.array([[0, 1], [2, 3]], np.int32)
    L = 2 * page
    kf = jnp.zeros((P, h, page, d), jnp.float32)
    vf = jnp.zeros((P, h, page, d), jnp.float32)
    kq, vq = _pool(P, h, page, d), _pool(P, h, page, d)
    # fill 12 positions per row through the SAME append path
    for pos in range(12):
        kn = rng.normal(0, 1.0, (b, h, d)).astype(np.float32)
        vn = rng.normal(0, 1.0, (b, h, d)).astype(np.float32)
        kf, vf = append_paged_kv(kf, vf, kn, vn, tables,
                                 np.full(b, pos, np.int32))
        kq, vq = append_paged_kv(kq, vq, kn, vn, tables,
                                 np.full(b, pos, np.int32))
    ctx = np.array([12, 12], np.int32)
    q1 = rng.normal(0, 1.0, (b, h, d)).astype(np.float32)
    of = np.asarray(paged_decode_attention(q1, kf, vf, tables, ctx))
    oq = np.asarray(paged_decode_attention(q1, kq, vq, tables, ctx))
    assert np.allclose(of, oq, atol=0.15)
    qs = rng.normal(0, 1.0, (b, 3, h, d)).astype(np.float32)
    starts = np.array([4, 6], np.int32)
    pf = np.asarray(paged_prefill_attention(qs, kf, vf, tables, starts))
    pq = np.asarray(paged_prefill_attention(qs, kq, vq, tables, starts))
    assert np.allclose(pf, pq, atol=0.15)
    # the verify op is the same gather machinery (spec decode reads it)
    vv = np.asarray(paged_verify_attention(qs, kq, vq, tables, starts))
    assert np.array_equal(pq, vv)
    # dense debug view dequantizes too
    kg, _ = gather_paged_kv(kq, vq, tables, L)
    kg_f, _ = gather_paged_kv(kf, vf, tables, L)
    assert np.allclose(np.asarray(kg), np.asarray(kg_f), atol=0.05)


def test_cow_copy_pages_carries_scales():
    pool = _pool()
    tables = np.array([[0, 1]], np.int32)
    rng = np.random.default_rng(4)
    x = rng.normal(0, 2.0, (1, 2, 4)).astype(np.float32)
    k1, v1 = append_paged_kv(pool, _pool(), x, x, tables,
                             np.array([3], np.int32))
    k2, v2 = copy_pages(k1, v1, 0, 2)
    assert np.array_equal(np.asarray(k2.data[2]), np.asarray(k2.data[0]))
    assert np.array_equal(np.asarray(k2.scale[2]), np.asarray(k2.scale[0]))
    assert np.asarray(k2.scale[2]).any()        # a real scale traveled


def test_chain_export_import_roundtrip_with_scales():
    """gather/scatter_chain_pages + gather_chain_scales: the migration
    halves round-trip the int8 block format bit-exactly (the codec dtype
    round trip the PTKV1 artifact rides on)."""
    rng = np.random.default_rng(5)
    pool = _pool(P=6)
    tables = np.array([[0, 1, 2]], np.int32)
    for pos in range(20):
        x = rng.normal(0, 1.0, (1, 2, 4)).astype(np.float32)
        pool, _ = append_paged_kv(pool, _pool(P=6), x, x, tables,
                                  np.array([pos], np.int32))
    kv = [(pool, pool)]
    blocks = [0, 1, 2]
    pages = gather_chain_pages(kv, blocks)
    scales = gather_chain_scales(kv, blocks)
    assert pages[0][0].dtype == np.int8
    assert scales is not None and scales[0][0].shape == (3, 2)
    dst = [( _pool(P=6), _pool(P=6) )]
    out = scatter_chain_pages(dst, [3, 4, 5], pages, scales=scales)
    (ko, vo) = out[0]
    assert np.array_equal(np.asarray(ko.data[3:6]),
                          np.asarray(pool.data[0:3]))
    assert np.array_equal(np.asarray(ko.scale[3:6]),
                          np.asarray(pool.scale[0:3]))
    # fp pools report no scales (the format marker the codec branches on)
    assert gather_chain_scales([(jnp.zeros((2, 2, 8, 4), jnp.float32),) * 2],
                               [0]) is None
    with pytest.raises(ValueError, match="scales"):
        scatter_chain_pages(dst, [3], [(pages[0][0][:1], pages[0][1][:1])])


def test_engine_int8_init_and_gauge():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.observability import engine_collector

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    eng = ContinuousBatchingEngine(LlamaForCausalLM(cfg), max_batch=2,
                                   max_len=32, page_size=8,
                                   kv_cache="int8")
    k0 = eng.caches["kv"][0][0]
    assert isinstance(k0, QuantizedKVPool) and str(k0.dtype) == "int8"
    assert eng._kv_quant_blocks == k0.shape[0]
    fams = {f.name: f for f in engine_collector(eng)()}
    assert fams["pt_kv_quant_blocks"].samples[0][2] == float(k0.shape[0])


# ---------------------------------------------------------------------------
# engine waves (slow): determinism, migration, warm/cold under int8
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


def _requests(cfg, seed=41):
    rng = np.random.default_rng(seed)
    kws = []
    for i in range(4):
        p = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        kw = dict(prompt_ids=p, max_new_tokens=8, seed=700 + i)
        if i % 2 == 1:
            kw.update(temperature=0.9)
        kws.append(kw)
    return kws


def _run(eng, kws, max_steps=500):
    from paddle_tpu.inference.serving import Request

    reqs = [Request(**kw) for kw in kws]
    for r in reqs:
        eng.add_request(r)
    eng.run_until_done(max_steps=max_steps)
    return [list(r.tokens) for r in reqs]


@pytest.mark.slow   # two int8 engine compiles — the quant math itself is
#                     pinned fast above
def test_int8_engine_deterministic_and_warm_cold(model):
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    cfg, m = model
    kws = _requests(cfg)

    def build():
        return ContinuousBatchingEngine(
            m, max_batch=2, max_len=32, page_size=8, block_size=2,
            kv_cache="int8",
            prefix_cache=PrefixCacheConfig(extra_blocks=4))

    a, b = build(), build()
    sa = _run(a, kws)
    assert sa == _run(b, kws)           # deterministic across engines
    warm = _run(a, kws)                 # warm radix re-serve (greedy AND
    assert warm == sa                   # seeded) is byte-identical too
    assert a.stats["hit_tokens"] > 0


@pytest.mark.slow   # one spec+int8 engine pair — the composition pin
def test_spec_plus_int8_is_deterministic_and_warm_cold(model):
    """Speculative decoding over int8 pools: rejected-draft appends feed
    the monotone block scales, so spec+int8 may differ from NON-spec int8
    in the last quantization bit (documented on SpecConfig) — but the
    composition stays fully deterministic: identical engines and warm
    re-admissions reproduce the same bytes."""
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, SpecConfig)

    cfg, m = model
    # all-greedy wave: a block containing any sampled row keeps the scan
    # mega-step, and this pin needs the spec path to actually run
    kws = [dict(kw, temperature=0.0) for kw in _requests(cfg)]

    def build():
        return ContinuousBatchingEngine(
            m, max_batch=2, max_len=32, page_size=8, block_size=2,
            kv_cache="int8", speculative=SpecConfig(k=3),
            prefix_cache=PrefixCacheConfig(extra_blocks=4))

    a, b = build(), build()
    sa = _run(a, kws)
    assert sa == _run(b, kws)           # engine-to-engine determinism
    assert _run(a, kws) == sa           # warm radix re-serve identical
    assert a.stats["spec_steps"] > 0    # the spec path actually ran


@pytest.mark.slow   # tiered migration over int8 pools (codec + 2 engines)
def test_int8_chains_migrate_and_resume(model, tmp_path):
    from paddle_tpu.inference.disagg import TieredRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)

    cfg, m = model
    kws = _requests(cfg, seed=43)

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2,
                                        prefix_cache=True, kv_cache="int8")

    refs = _run(build(), kws)
    tiered = TieredRouter(build, build, str(tmp_path), num_prefill=1,
                          num_decode=1)
    reqs = [Request(**kw) for kw in kws]
    try:
        for r in reqs:
            tiered.submit(r)
        tiered.run_until_done(max_steps=2000)
        assert tiered.stats["migrations"] >= 1
    finally:
        tiered.close()
    assert [list(r.tokens) for r in reqs] == refs
