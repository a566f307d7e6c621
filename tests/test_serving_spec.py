"""Speculative multi-token decoding in the mega-step
(inference/serving.py ``speculative=SpecConfig(...)`` — docs/SERVING.md
"Speculative decode").

The contract under test: greedy speculative token streams are
BYTE-IDENTICAL to the non-speculative mega-step — drafts only change how
many tokens a dispatch emits, never which — across slot widths, warm/cold
radix admissions, COW divergence, migration and crash replay, with
acceptance > 0 on a repetitive workload. Engine waves are slow-marked
(tier-1 sits near its 870 s ceiling); the FAST pins below cover the pure
accept/reject math and the device drafter with no model or compile.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          KVCacheConfig, PrefixCacheConfig,
                                          Request, SpecConfig, ngram_draft,
                                          spec_accept)


# ---------------------------------------------------------------------------
# FAST pins: pure host-testable accept/reject + drafter math (no model)
# ---------------------------------------------------------------------------

def test_spec_accept_longest_prefix_plus_bonus():
    drafts = np.array([[5, 6, 7],      # all accepted -> 3 drafts + bonus
                       [5, 9, 7],      # reject at 1 -> 1 draft + bonus
                       [1, 2, 3]])     # reject at 0 -> bonus only
    targets = np.array([[5, 6, 7, 8],
                        [5, 6, 7, 8],
                        [9, 9, 9, 9]])
    caps = np.array([10, 10, 10])
    out, emit, n_acc = (np.asarray(x) for x in
                        spec_accept(drafts, targets, caps))
    assert list(n_acc) == [3, 1, 0]
    assert list(emit) == [4, 2, 1]
    # emitted tokens == accepted drafts + the model's own next token
    assert list(out[0][:4]) == [5, 6, 7, 8]
    assert list(out[1][:2]) == [5, 6]
    assert list(out[2][:1]) == [9]


def test_spec_accept_caps_clamp_and_mask():
    drafts = np.array([[5, 6], [5, 6]])
    targets = np.array([[5, 6, 7], [5, 6, 7]])
    out, emit, n_acc = (np.asarray(x) for x in
                        spec_accept(drafts, targets, np.array([2, 0])))
    assert list(emit) == [2, 0]        # cap truncates; cap 0 masks the row
    assert list(out[0][:2]) == [5, 6]  # truncation keeps the draft prefix


def test_ngram_draft_continuation_and_fallback():
    H, k, n = 8, 3, 2
    # ring holds tokens [1,2,3,4,1,2] (hlen=6 < H: slots 0..5), last=3 ->
    # tail (2, 3) matched at global positions 1..2, continuation 4, 1, 2
    hist = np.zeros((2, H), np.int32)
    hist[0, :6] = [1, 2, 3, 4, 1, 2]
    hlen = np.array([6, 0], np.int32)
    last = np.array([3, 7], np.int32)
    drafts = np.asarray(ngram_draft(hist, hlen, last, k, n))
    assert list(drafts[0]) == [4, 1, 2]
    # row 1 has no history -> fallback repeats the last token
    assert list(drafts[1]) == [7, 7, 7]


def test_ngram_draft_ring_wraparound():
    H, k, n = 4, 2, 2
    # 6 tokens written through a 4-ring: global g at slot g % 4 ->
    # ring holds [4, 5, 2, 3] for stream [.., 2, 3, 4, 5]; last = 2 ->
    # window is [2, 3, 4, 5, 2]; tail (5, 2) has no earlier match ->
    # fallback; tail (2, 3)... use last=3 after stream [1,2,3,4,2,3]:
    stream = [1, 2, 3, 4, 2, 3]
    hist = np.zeros((1, H), np.int32)
    for g, t in enumerate(stream):
        hist[0, g % H] = t
    hlen = np.array([len(stream)], np.int32)
    last = np.array([4], np.int32)
    # window (last H + last_tok) = [3, 4, 2, 3, 4]; tail (3, 4) matches at
    # window start 0 -> continuation [2, 3]
    drafts = np.asarray(ngram_draft(hist, hlen, last, k, n))
    assert list(drafts[0]) == [2, 3]


def test_spec_config_validation():
    with pytest.raises(ValueError, match="k .* must be >= 1|>= 1"):
        _Cfg = SpecConfig(k=0)
        _validate_engine(speculative=_Cfg)
    with pytest.raises(ValueError, match="history .* too short"):
        _validate_engine(speculative=SpecConfig(k=4, history=4))
    # one decode family since PR 30: the flag chooses nothing, and asking
    # for the family that left is refused by name
    with pytest.raises(ValueError, match="fused=False.*PR 30"):
        _validate_engine(speculative=True, fused=False)
    with pytest.raises(ValueError, match="unsupported KV cache dtype"):
        KVCacheConfig(dtype="int4")


def _validate_engine(**kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return ContinuousBatchingEngine(LlamaForCausalLM(cfg), max_batch=2,
                                    max_len=32, page_size=8, **kw)


def test_spec_seed_ring_layout():
    """Activation seeds lay prompt tokens at ring slot g % H so the spec
    program's ring arithmetic continues seamlessly, including prompts
    longer than the ring."""
    eng = _validate_engine(speculative=SpecConfig(history=8))
    row, hlen = eng._spec_seed(np.arange(100, 112, dtype=np.int32))
    assert hlen == 12
    # last 8 tokens (global 4..11) at slots 4%8..11%8
    expect = np.zeros(8, np.int32)
    for g in range(4, 12):
        expect[g % 8] = 100 + g
    assert list(row) == list(expect)
    # migration seed appends delivered tokens after the prompt
    row2, hlen2 = eng._spec_seed(np.arange(3, dtype=np.int32),
                                 extra=[7, 8])
    assert hlen2 == 5 and row2[3] == 7 and row2[4] == 8


def test_spec_metrics_families_render_at_zero():
    """pt_spec_* + pt_kv_quant_blocks are REQUIRED families: they must
    render on a fresh engine (zeros) — scrape dashboards never lose them."""
    from paddle_tpu.observability import engine_collector

    eng = _validate_engine(speculative=True)
    fams = {f.name: f for f in engine_collector(eng)()}
    for name in ("pt_spec_proposed_total", "pt_spec_accepted_total",
                 "pt_spec_acceptance_rate", "pt_kv_quant_blocks"):
        assert name in fams, sorted(fams)
        assert fams[name].samples


def test_verify_window_keeps_the_row_append_where_the_chunk_writes_pages():
    """The verify window runs the packed chunk's layer body at ANY position,
    so it may not write whole pages: even with a window as long as a page,
    in pools the page form takes (two KV heads of 64, lane-dense), its
    lowered append is the row form (one index a token and head group) and
    the packed chunk's the page form (one index a page)."""
    import re

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=128,
                           num_attention_heads=2, num_key_value_heads=2)
    model = LlamaForCausalLM(cfg)
    caches = model._init_paged_caches(2, 32, page_size=8, num_blocks=12)
    pool = caches["kv"][0][0].shape
    assert pool[1:] == (1, 8, 128)
    toks = jnp.zeros((2, 8), jnp.int32)            # K + 1 = 8 = one page
    pos = jnp.asarray([8, 16], jnp.int32)

    def scatters(fn):
        text = jax.jit(fn).lower(toks, caches, pos).as_text()
        return re.findall(r"\}\) : \(tensor<[^>]*>, (tensor<[^>]*>), "
                          r"(tensor<[^>]*>)\) -> tensor", text)

    by_row = ("tensor<16x1x3xi32>", "tensor<16x1x128xf32>")
    by_page = ("tensor<2x1xi32>", "tensor<2x1x8x128xf32>")
    assert scatters(lambda t, c, p: model.paged_verify_step(t, c, p)[1]) == [
        by_row, by_row]
    assert scatters(model.paged_prefill_chunk) == [by_page, by_page]


# ---------------------------------------------------------------------------
# engine waves (slow): byte-identity across widths/warm/cold/COW/replay
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    return cfg, LlamaForCausalLM(cfg)


def _wave(cfg, rng_seed=300):
    rng = np.random.default_rng(rng_seed)
    motif = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    # prompt 0 is long-repetitive with a long continuation (the drafter's
    # food — greedy streams of a tiny model settle into loops the n-gram
    # lookup then predicts); 16/24 are full-page multiples so a warm
    # re-serve takes the full-prompt-hit COW path
    prompts = [np.tile(motif, 6),
               np.tile(motif, 4),
               rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32),
               rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)]
    kws = [dict(max_new_tokens=24), dict(max_new_tokens=10),
           dict(max_new_tokens=8), dict(max_new_tokens=6)]
    return prompts, kws


def _serve(eng, prompts, kws, stagger=True):
    reqs = [Request(p, **k) for p, k in zip(prompts, kws)]
    head, tail = (reqs[:2], reqs[2:]) if stagger else (reqs, [])
    for r in head:
        eng.add_request(r)
    eng.step()
    for r in tail:
        eng.add_request(r)
    eng.run_until_done(max_steps=800)
    return [list(r.tokens) for r in reqs]


@pytest.mark.slow   # several engine compiles (spec + nonspec, two widths,
#                     prefix on/off) — fast pins above cover the math
def test_spec_byte_identity_cross_widths_warm_cold_cow(model):
    cfg, m = model
    prompts, kws = _wave(cfg)
    ref = _serve(ContinuousBatchingEngine(
        m, max_batch=4, max_len=64, page_size=8, block_size=2),
        prompts, kws)
    # width 4, prefix off
    s4 = ContinuousBatchingEngine(
        m, max_batch=4, max_len=64, page_size=8, block_size=2,
        speculative=SpecConfig(k=3))
    assert _serve(s4, prompts, kws) == ref
    # cross slot width (6 slots, different mega shape) + prefix cache:
    # cold then warm re-serve — the warm wave takes the full-prompt-hit
    # COW path for the repeated 16-token prompts
    s6 = ContinuousBatchingEngine(
        m, max_batch=6, max_len=64, page_size=8, block_size=2,
        speculative=SpecConfig(k=3),
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=12))
    cold = _serve(s6, prompts, kws)
    warm = _serve(s6, prompts, kws)
    assert cold == ref
    assert warm == ref
    assert s6.stats["cow_copies"] >= 1          # the COW path really ran
    # acceptance > 0 on the repetitive workload (the ISSUE acceptance pin)
    assert s4.stats["spec_accepted"] > 0
    assert s4.stats["spec_proposed"] > 0
    assert 0 < s4.stats["spec_steps"] < sum(
        k["max_new_tokens"] for k in kws)       # multi-token dispatches


@pytest.mark.slow   # supervisor replay recompiles the engine mid-test
def test_spec_crash_replay_byte_identical(model, tmp_path):
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import ServingSupervisor

    cfg, m = model
    prompts, kws = _wave(cfg)

    def build():
        return ContinuousBatchingEngine(
            m, max_batch=4, max_len=64, page_size=8, block_size=2,
            speculative=SpecConfig(k=3),
            prefix_cache=PrefixCacheConfig(extra_blocks=8))

    ref_eng = build()
    reqs = [Request(p, **k) for p, k in zip(prompts, kws)]
    for r in reqs:
        ref_eng.add_request(r)
    ref_eng.run_until_done(max_steps=800)
    refs = [list(r.tokens) for r in reqs]

    plan = FaultPlan(seed=5, specs=[
        FaultSpec("serving.step", "kill", at=3, count=1)])
    sup = ServingSupervisor(build, str(tmp_path / "j.jrnl"))
    reqs2 = [Request(p, **k) for p, k in zip(prompts, kws)]
    with plan:
        for r in reqs2:
            sup.submit(r)
        sup.run_until_done(max_steps=2000)
    assert plan.log, "the mid-decode kill never fired"
    assert sup.stats["recoveries"] >= 1
    assert [list(r.tokens) for r in reqs2] == refs


@pytest.mark.slow   # tiered-router migration wave (two engines + codec)
def test_spec_stream_survives_migration(model, tmp_path):
    """A chain exported mid-decode from a spec engine and spliced into
    another spec engine continues byte-identically — the migrated drafter
    ring is re-seeded from prompt + delivered tokens."""
    from paddle_tpu.inference.disagg import KVChainCodec

    cfg, m = model
    rng = np.random.default_rng(9)
    prompt = np.tile(rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
                     4)

    def build():
        return ContinuousBatchingEngine(
            m, max_batch=2, max_len=64, page_size=8, block_size=2,
            speculative=SpecConfig(k=3), prefix_cache=True)

    ref_eng = build()
    r0 = Request(prompt, max_new_tokens=16)
    ref_eng.add_request(r0)
    ref_eng.run_until_done(max_steps=400)
    ref = list(r0.tokens)

    src = build()
    r1 = Request(prompt, max_new_tokens=16)
    src.add_request(r1)
    src.step()                        # prefill + first tokens scheduled
    assert src.migration_ready() == [r1.rid]
    codec = KVChainCodec()
    art = codec.export_chain(src, r1.rid)
    src.withdraw_active(r1.rid)
    dst = build()
    twin = codec.import_chain(dst, art)
    dst.run_until_done(max_steps=400)
    assert list(twin.tokens) == ref


@pytest.mark.slow   # one spec engine wave with eos materialization
def test_spec_eos_and_mixed_sampling_fallback(model):
    cfg, m = model
    prompts, kws = _wave(cfg)

    def build(**kw):
        return ContinuousBatchingEngine(
            m, max_batch=4, max_len=64, page_size=8, block_size=2,
            **kw)

    # eos: pick a token the greedy stream actually emits so early-exit
    # fires inside a speculative dispatch
    ref0 = _serve(build(), prompts, kws, stagger=False)
    eos = ref0[0][4]
    kws_eos = [dict(k, eos_token_id=eos) for k in kws]
    ref = _serve(build(), prompts, kws_eos, stagger=False)
    got = _serve(build(speculative=SpecConfig(k=3)), prompts, kws_eos,
                 stagger=False)
    assert got == ref
    # mixed greedy + seeded sampling: sampled blocks keep the scan
    # mega-step; streams still match the non-spec engine exactly
    kws_mix = [dict(kws[0]), dict(kws[1], temperature=0.9, seed=7),
               dict(kws[2]), dict(kws[3], temperature=1.1, seed=3)]
    ref_mix = _serve(build(), prompts, kws_mix)
    got_mix = _serve(build(speculative=SpecConfig(k=3)), prompts, kws_mix)
    assert got_mix == ref_mix
