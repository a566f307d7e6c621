"""A page group a layer kind (ops/paged_attention.py ``PageGroups``,
docs/SERVING.md "Window and full layers"): a window pool smaller than
``max_len`` serves sequences to ``max_len``, pages come back while a
sequence lives, a hit / a shortened hit / a declined hit all serve the
tokens a cold engine serves, copy-on-write and eviction work per group,
admission defers when either group is short, every feature a window-mixed
model does not get is refused by name, and an all-full model still builds
one group and the programs it built before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from _afmoe_util import TINY, engine, seeded_model
from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                          KVChainCodec, PrefixCacheConfig,
                                          Request, SpecConfig)
from paddle_tpu.ops.paged_attention import LayerStateError, PageGroups

PAGE, WINDOW, MAX_LEN = 4, 16, 128


@pytest.fixture(scope="module")
def model():
    return seeded_model(5, "float32")[0]


@pytest.fixture(scope="module")
def greedy(model):
    """Greedy continuation by the whole forward pass, no cache: the tokens
    a cold engine serves."""
    fwd = jax.jit(lambda ids: model(ids))

    def run(prompt, n):
        seq = list(int(t) for t in prompt)
        for _ in range(n):
            ids = np.zeros((1, MAX_LEN), np.int32)
            ids[0, :len(seq)] = seq
            seq.append(int(np.asarray(fwd(jnp.asarray(ids)))[
                0, len(seq) - 1].argmax()))
        return seq[len(prompt):]

    return run


@pytest.fixture(scope="module")
def eng(model):
    """ONE engine for the module: its programs compile once; ``_drain``
    empties the trie between tests."""
    return engine(model)


def _ids(n, seed):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


def _drain(e):
    e.run_until_done(max_steps=500)
    e.finished()
    e._radix.evict_lru(e._alloc.num_blocks)


def _serve(e, prompts, max_new=10):
    reqs = [Request(p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        e.add_request(r)
    e.run_until_done(max_steps=500)
    e.finished()
    assert all(r.done and not r.failed for r in reqs)
    return [list(r.output) for r in reqs]


# ---- the groups an engine builds ---------------------------------------------

def test_the_window_pool_does_not_depend_on_max_len(model, eng):
    """Two groups; the window group's pool is max_batch x (ceil((window +
    chunk) / page) + 1) pages and its share of the extra ones, whatever
    ``max_len`` asks of the full group; one device table a group."""
    g = eng._groups
    assert [x.kind for x in g.groups] == ["full", "sliding"]
    per = -(-(WINDOW + 8) // PAGE) + 1
    assert g.windowed[0].slot_pages == per == 7
    assert g.windowed[0].num_blocks == 4 * per + -(-16 * per // 32)
    assert g.full.num_blocks == 4 * 32 + 16
    assert eng._alloc is g.full.alloc and eng._radix is g.radix
    tables = eng.caches["tables"]
    assert isinstance(tables, tuple) and len(tables) == 2
    s = eng.stats
    assert s["kv_groups"] == 2
    assert s["kv_pool_pages.sliding"] == g.windowed[0].num_blocks
    assert s["paged_kernel_layers_by_group.full"] \
        + s["paged_kernel_layers_by_group.sliding"] \
        == s["paged_kernel_layers"]
    # the chunk kernel serves no layer off the TPU, by group as in all
    assert s["chunk_kernel_layers_by_group.full"] \
        == s["chunk_kernel_layers_by_group.sliding"] \
        == s["chunk_kernel_layers"] == 0
    # the pools: a window layer's is the smaller
    full_pages = eng.caches["kv"][3][0].shape[0]
    window_pages = eng.caches["kv"][0][0].shape[0]
    assert window_pages < 4 * 32 <= full_pages
    other = ContinuousBatchingEngine(
        model, max_batch=4, max_len=512, page_size=PAGE, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8))
    assert other._groups.windowed[0].num_blocks == 4 * per
    assert other._groups.full.num_blocks == 4 * 128


def test_sequences_run_to_max_len_on_a_window_pool_smaller_than_it(
        eng, greedy):
    """Four sequences together, one of them to 127 of ``max_len`` 128: the
    window group holds 31 pages where one such sequence alone needs 32 of
    the full group; every stream is the whole forward pass's."""
    try:
        prompts = [_ids(n, 10 + n) for n in (5, 30, 61, 107)]
        got = _serve(eng, prompts, max_new=20)
        for p, out in zip(prompts, got):
            assert out == greedy(p, 20), len(p)
        assert eng.stats["window_pages_released"] > 0
    finally:
        _drain(eng)


def test_pages_come_back_while_a_sequence_lives(eng):
    """A 90-token prompt, stepped by hand: mid-prefill and mid-decode the
    sequence maps no more window pages than ``slot_pages``, the counter of
    pages given back grows while it lives, and what it maps lies at the
    window's end."""
    try:
        g = eng._groups
        wg = g.windowed[0]
        released0 = g.released
        req = Request(_ids(90, 3), max_new_tokens=30, eos_token_id=-1)
        eng.add_request(req)
        seen = []
        for _ in range(200):
            eng.step()
            if req.done:
                break
            held = g._held[0][0]
            assert held is not None and len(held) <= wg.slot_pages
            assert wg.in_use <= wg.slot_pages
            seen.append((g.released - released0, min(held), max(held)))
        assert req.done and not req.failed
        # pages came back before the end, and the held range moved on
        assert seen[0][0] < seen[-1][0] and seen[-1][0] >= 15
        assert seen[-1][1] > seen[0][1]
        # all of them are back now
        assert wg.in_use == 0
        assert eng.stats["window_pages_released"] == g.released
    finally:
        _drain(eng)


# ---- the hit rule ------------------------------------------------------------------

@pytest.mark.parametrize("evict,want_hit,shortened,declined", [
    (0, 40, 0, 0),        # the window group covers pages [6, 10): honoured
    (3, 32, 1, 0),        # pages 8-10 gone: shortened to 8 pages
    (11, 0, 1, 1),        # nothing left of it: declined
])
def test_a_hit_a_shortened_hit_and_a_declined_hit_serve_cold_tokens(
        eng, greedy, evict, want_hit, shortened, declined):
    try:
        g = eng._groups
        first = _ids(46, 21)
        _serve(eng, [first], max_new=6)          # 11 whole pages in the trie
        assert len(g._side[0]) == 11
        assert g._evict(0, evict) == evict       # deepest of the path first
        s0 = dict(eng.stats)
        again = np.concatenate([first[:40], _ids(7, 22)])
        out = _serve(eng, [again], max_new=12)[0]
        assert out == greedy(again, 12)
        s1 = eng.stats
        assert s1["hit_tokens"] - s0["hit_tokens"] == want_hit
        assert s1["prefix_hits_shortened"] - s0["prefix_hits_shortened"] \
            == shortened
        assert s1["prefix_declined_admissions"] \
            - s0["prefix_declined_admissions"] == declined
        assert s1["prefix_hit_admissions"] - s0["prefix_hit_admissions"] \
            == (want_hit > 0)
    finally:
        _drain(eng)


def test_honour_reads_the_window_groups_cover():
    """``PageGroups.honour`` on hand-made chains: the longest head whose
    last ``window`` tokens the window group still holds."""
    g = PageGroups([("full", None), ("sliding", 16)], max_batch=2,
                   max_len=64, page_size=4, chunk=8, block=4)
    chain = list(range(100, 112))                # 12 pages of the full group
    side = g._side[0]
    have = lambda pages: (side.clear(), side.update(
        {chain[i]: 50 + i for i in pages}))
    have(range(12))
    assert g.honour(chain) == chain
    have(range(4, 12))                           # the head evicted: fine
    assert g.honour(chain) == chain and g.honour(chain[:8]) == chain[:8]
    assert g.honour(chain[:6]) == []             # it reads pages [2, 6)
    have([i for i in range(12) if i != 9])       # a gap inside the window
    assert g.honour(chain) == chain[:9]
    have(range(0, 3))
    assert g.honour(chain) == chain[:3]
    have([])
    assert g.honour(chain) == [] and g.shortened == 4
    with pytest.raises(ValueError, match="full group"):
        PageGroups([("sliding", 16)], max_batch=1, max_len=8, page_size=4,
                   chunk=4, block=4)


# ---- copy-on-write and eviction, a group ---------------------------------------------

def test_a_full_prompt_hit_copies_the_last_page_in_both_groups(eng, greedy):
    try:
        prompt = _ids(40, 31)                    # ten whole pages
        cold = _serve(eng, [prompt], max_new=8)[0]
        assert cold == greedy(prompt, 8)
        s0 = dict(eng.stats)
        src_w = eng._groups._side[0][eng._radix.chain(prompt)[-1]]
        warm = _serve(eng, [prompt], max_new=8)[0]
        assert warm == cold
        assert eng.stats["cow_copies"] - s0["cow_copies"] == 1
        assert eng.stats["hit_tokens"] - s0["hit_tokens"] == 40
        # the shared window page is still the trie's, untouched and idle
        assert eng._groups._side[0][eng._radix.chain(prompt)[-1]] == src_w
        assert eng._groups.windowed[0].alloc.refcount(src_w) == 0
    finally:
        _drain(eng)


def test_eviction_works_a_group(eng):
    """The window group gives up its own cached pages under pressure and
    the trie keeps the full group's; the trie giving a node up frees both
    groups' pages."""
    try:
        g, wg = eng._groups, eng._groups.windowed[0]
        _serve(eng, [_ids(46, 41), _ids(46, 42)], max_new=4)
        assert len(g._side[0]) == 22 and len(eng._radix) == 22
        assert wg.alloc.free_blocks == wg.num_blocks - 22
        # a long prompt needs the window pages: the idle ones go, node by
        # node, and the full group's chains stay whole
        _serve(eng, [_ids(100, 43)], max_new=4)
        assert len(eng._radix) == 22 + 25
        assert wg.alloc.free_blocks + len(g._side[0]) == wg.num_blocks
        eng._radix.evict_lru(eng._alloc.num_blocks)
        assert len(eng._radix) == 0 and not g._side[0] and not g._back[0]
        assert wg.alloc.free_blocks == wg.num_blocks
        assert eng._alloc.free_blocks == eng._alloc.num_blocks
    finally:
        _drain(eng)


@pytest.mark.parametrize("short", ["full", "sliding"])
def test_admission_defers_when_either_group_is_short(eng, greedy, short):
    try:
        g = eng._groups
        alloc = (g.full if short == "full" else g.windowed[0]).alloc
        held = alloc.hold(alloc.free_blocks)
        assert held and alloc.free_blocks == 0
        prompt = _ids(26, 51)
        req = Request(prompt, max_new_tokens=8)
        eng.add_request(req)
        for _ in range(3):
            eng.step()
        assert len(eng._queue) == 1 and not eng._occupied and not req.output
        other = (g.windowed[0] if short == "full" else g.full).alloc
        assert other.free_blocks == other.num_blocks    # nothing kept
        assert alloc.release_held() == held
        eng.run_until_done(max_steps=200)
        assert list(req.output) == greedy(prompt, 8)
    finally:
        _drain(eng)


# ---- what a window-mixed model does not get ---------------------------------------------

@pytest.mark.parametrize("what,kw", [
    ("speculative decoding", dict(speculative=SpecConfig(k=2))),
    ("an int8 KV pool", dict(kv_cache="int8")),
    ("a tp mesh", dict(mesh=1)),
    ("without a prefix cache", dict(prefix_cache=None)),
])
def test_an_engine_refuses_by_name(model, what, kw):
    with pytest.raises(LayerStateError, match="PT-SRV-009") as err:
        engine(model, **kw)
    assert what in str(err.value) and "sliding" in str(err.value)


def test_export_and_migration_are_refused_by_name(model, eng):
    try:
        req = Request(_ids(20, 61), max_new_tokens=12, eos_token_id=-1)
        eng.add_request(req)
        for _ in range(6):
            eng.step()
            if eng.migration_ready():
                break
        assert eng.migration_ready() == [req.rid]
        with pytest.raises(LayerStateError, match="chain export"):
            KVChainCodec().export_chain(eng, req.rid)
        with pytest.raises(LayerStateError, match="migrated chain"):
            eng.admit_migrated(Request(_ids(8, 62), max_new_tokens=4), [0],
                               9, 5)
        with pytest.raises(LayerStateError, match="speculative"):
            model.paged_verify_step(None, None, None)
    finally:
        _drain(eng)


# ---- an all-full model is as it was ---------------------------------------------------

def test_an_all_full_model_builds_one_group():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    e = ContinuousBatchingEngine(
        m, max_batch=2, max_len=64, page_size=8,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=3))
    g = e._groups
    assert g.single and [x.kind for x in g.groups] == ["full"]
    assert g.full.num_blocks == 2 * 8 + 3 and e._park == 19
    assert not isinstance(e.caches["tables"], tuple)
    assert e.caches["tables"].shape == (2, 8)
    assert e.stats["kv_groups"] == 1
    assert e.stats["kv_pool_pages.full"] == 19
    prompt = _ids(24, 71) % 100
    out = _serve(e, [prompt], max_new=6)[0]
    ref = np.asarray(m.generate(paddle.to_tensor(prompt[None]),
                                max_new_tokens=6, temperature=0.0))[0]
    assert out == [int(t) for t in ref[-6:]]
    assert e.stats["window_pages_released"] == 0
    e.step()                    # the gauge is set a step, at admission
    assert e.stats["kv_pages_in_use.full"] == 0
    # an afmoe model without a sliding layer declares the one group too
    full_only = seeded_model(5, "float32", cfg=dict(
        TINY, layer_types=["full_attention"] * 5))[0]
    assert engine(full_only)._groups.single
