"""State that is not pages (docs/SERVING.md): one test a line of the contract
between the serving engine and a model whose layers keep a fixed block a
sequence (``ops.paged_attention.PageState``), on ``models/lfm2`` at the tiny
size in float32; and the streams of a ``models/llama`` engine held to the
parent commit's, byte for byte."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from _lfm2_util import engine, reference_logits, seeded_model, serve
from paddle_tpu.inference.serving import (LayerStateError, PrefixCacheConfig,
                                          Request)
from paddle_tpu.ops.paged_attention import (PageState, layer_kinds,
                                            page_state_read, pool_pages)

PAGE = 4


@pytest.fixture(scope="module")
def lfm2():
    return seeded_model(5, "float32")


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


def _greedy(prompt, n=8, **kw):
    return Request(np.asarray(prompt, np.int32), max_new_tokens=n, **kw)


def _sampled(prompt, n=8, seed=11, **kw):
    return Request(np.asarray(prompt, np.int32), max_new_tokens=n,
                   temperature=0.7, top_p=0.95, seed=seed, **kw)


# ---- the model says what its layers keep ------------------------------------

def test_model_states_what_each_layer_keeps(lfm2):
    model = lfm2[0]
    caches = model._init_paged_caches(2, 16, page_size=PAGE, num_blocks=11)
    assert layer_kinds(caches["kv"]) == ["state", "kv", "state", "state",
                                         "state"]
    ring = caches["kv"][0]
    assert isinstance(ring, PageState) and ring.page == PAGE
    # the pages asked for, rounded up to the tile's rows (pool_pages)
    n = pool_pages(11, ring.ring.dtype)
    assert n % 8 == 0 and 11 <= n < 11 + 16
    assert ring.ring.shape == (n, 3, 64)           # conv_L_cache slots a page
    k, v = caches["kv"][1]
    assert k.shape == v.shape == (n, 2, PAGE, 16)
    eng = engine(model)
    assert eng._state_layers == [0, 2, 3, 4]
    assert eng.stats["state_snapshot_bytes"] == 4 * ring.ring[0].nbytes * (
        pool_pages(4 * 16 + 8 + 1, ring.ring.dtype))


# ---- decode ---------------------------------------------------------------

def test_decode_blocks_carry_state_and_parked_rows_are_inert(lfm2):
    """A request's stream is the same alone (three parked rows beside it)
    and among three others that start, finish and free their slots around
    it; both are the reference's greedy stream."""
    model, top, layer = lfm2
    prompt = _ids(13, 1)
    alone = serve(engine(model), [_greedy(prompt, 14)])[0]
    crowd = serve(engine(model), [
        _greedy(_ids(9, 2), 3), _greedy(prompt, 14), _sampled(_ids(21, 3), 5),
        _greedy(_ids(6, 4), 9)])[1]
    assert alone == crowd
    lg = reference_logits(np.concatenate([prompt, alone]), top, layer)
    rows = lg[len(prompt) - 1: len(prompt) - 1 + len(alone)]
    assert (rows.max(-1) - rows[np.arange(len(alone)), alone]).max() < 1e-3


# ---- chunked and packed prefill ----------------------------------------------

def _prefill(model, prompt, chunk, order=None, pad_id=0, num_blocks=40):
    """Prefill ``prompt`` through ``paged_prefill_chunk`` as the engine's
    pack does: one row a chunk of ``chunk`` tokens, all in one call, in the
    given row order, the last chunk padded with ``pad_id``; then the
    first-token re-step. Returns (logits of the re-step, caches)."""
    L = len(prompt)
    maxp = 16
    caches = model._init_paged_caches(1, maxp * PAGE, page_size=PAGE,
                                      num_blocks=num_blocks)
    table = np.arange(3, 3 + maxp, dtype=np.int32)       # not page 0..2
    starts = list(range(0, L, chunk))
    order = list(range(len(starts))) if order is None else order
    ids = np.full((len(starts), chunk), pad_id, np.int32)
    valid = np.zeros(len(starts), np.int32)
    n_real = -(-L // PAGE)
    row = np.full(maxp, num_blocks - 1, np.int32)        # parked past it
    row[:n_real] = table[:n_real]
    for r, j in enumerate(order):
        piece = prompt[starts[j]: starts[j] + chunk]
        ids[r, :len(piece)] = piece
        valid[r] = len(piece)
    sub = {"kv": caches["kv"], "valid": jnp.asarray(valid),
           "tables": jnp.asarray(np.tile(row, (len(starts), 1)))}
    sub = model.paged_prefill_chunk(
        jnp.asarray(ids), sub, jnp.asarray([starts[j] for j in order],
                                           jnp.int32))
    step = {"kv": sub["kv"], "tables": jnp.asarray(table[None])}
    logits, step = model.paged_token_step(
        jnp.asarray(prompt[-1:]), step, jnp.asarray([L - 1], jnp.int32))
    return np.asarray(logits[0]), step, table


def _rings(caches, table, upto):
    """What the state layers hold for positions < upto, by position."""
    out = []
    for e in caches["kv"]:
        if isinstance(e, PageState):
            pos = jnp.arange(max(upto - 2, 1), upto + 1, dtype=jnp.int32)
            out.append(np.asarray(page_state_read(
                e, jnp.asarray(np.tile(table, (len(pos), 1))), pos, 2)))
    return out


def test_packed_chunks_in_any_row_order_equal_one_pass(lfm2):
    """Several chunks of one prompt in one program, rows scrambled, give the
    logits and the state of one pass over the whole prompt, and the
    reference's logits."""
    model, top, layer = lfm2
    prompt = _ids(37, 5)
    one, c_one, table = _prefill(model, prompt, 40)
    want = reference_logits(prompt, top, layer)[-1]
    assert np.abs(one - want).max() < 1e-4
    for order in ([0, 1, 2, 3, 4], [3, 0, 4, 2, 1], [4, 3, 2, 1, 0]):
        got, c, _ = _prefill(model, prompt, 8, order)
        np.testing.assert_allclose(got, one, atol=2e-5)
        for a, b in zip(_rings(c, table, 37), _rings(c_one, table, 37)):
            np.testing.assert_allclose(a, b, atol=1e-6)


def test_padded_tail_of_a_last_chunk_leaves_no_trace(lfm2):
    """Whatever ids pad the last chunk, the state rings of the prompt's
    pages come out the same to the bit, and so do the logits."""
    model = lfm2[0]
    prompt = _ids(37, 6)
    a, ca, table = _prefill(model, prompt, 8, pad_id=0)
    b, cb, _ = _prefill(model, prompt, 8, pad_id=77)
    np.testing.assert_array_equal(a, b)
    pages = table[: -(-37 // PAGE)]
    for ea, eb in zip(ca["kv"], cb["kv"]):
        if isinstance(ea, PageState):
            np.testing.assert_array_equal(np.asarray(ea.ring[pages]),
                                          np.asarray(eb.ring[pages]))


def test_first_token_restep_is_idempotent_for_the_state(lfm2):
    """Re-stepping the last prompt position again changes neither the rings
    nor the logits: position p reads slots p-1, p-2 and writes slot p."""
    model = lfm2[0]
    prompt = _ids(22, 7)
    first, c1, table = _prefill(model, prompt, 8)
    again, c2 = model.paged_token_step(
        jnp.asarray(prompt[-1:]), {"kv": c1["kv"], "tables": c1["tables"]},
        jnp.asarray([len(prompt) - 1], jnp.int32))
    np.testing.assert_allclose(np.asarray(again[0]), first, atol=1e-6)
    for a, b in zip(c1["kv"], c2["kv"]):
        if isinstance(a, PageState):
            np.testing.assert_allclose(np.asarray(a.ring),
                                       np.asarray(b.ring), atol=1e-7)


# ---- release and reuse --------------------------------------------------------

def test_a_released_slots_state_does_not_leak(lfm2):
    """One slot, no cache hits: the second request, on the first one's slot
    and recycled pages, streams what it streams on a fresh engine."""
    model = lfm2[0]
    a, b = _ids(19, 8), _ids(11, 9)
    fresh = serve(engine(model, max_batch=1), [_sampled(b, 12)])[0]
    eng = engine(model, max_batch=1)
    serve(eng, [_greedy(a, 10)])
    assert serve(eng, [_sampled(b, 12)])[0] == fresh
    assert eng.stats["hit_tokens"] == 0


# ---- prefix hits ---------------------------------------------------------------

@pytest.mark.parametrize("shared", [8, 12, 10],
                         ids=["two-pages", "three-pages", "unaligned"])
def test_prefix_hit_equals_no_cache(lfm2, shared):
    """A request admitted onto cached pages resumes every conv layer from
    the rings kept with them: same stream as without the cache, the
    reference's logits, and the counters say so."""
    model, top, layer = lfm2
    head = _ids(shared, 20)
    warm, cold = np.concatenate([head, _ids(9, 21)]), \
        np.concatenate([head, _ids(13, 22)])
    want = serve(engine(model, prefix_cache=PrefixCacheConfig()),
                 [_sampled(cold, 10)])[0]
    eng = engine(model)
    serve(eng, [_greedy(warm, 4)])
    s0 = dict(eng.stats)
    got = serve(eng, [_sampled(cold, 10)])[0]
    assert got == want
    hit = (shared // PAGE) * PAGE
    assert eng.stats["hit_tokens"] - s0["hit_tokens"] == hit
    assert eng.stats["prefix_hit_admissions"] - s0[
        "prefix_hit_admissions"] == 1
    greedy = serve(eng, [_greedy(cold, 6)])[0]      # a second hit, greedy
    lg = reference_logits(np.concatenate([cold, greedy]), top, layer)
    rows = lg[len(cold) - 1: len(cold) - 1 + len(greedy)]
    assert (rows.max(-1) - rows[np.arange(6), greedy]).max() < 1e-3


def test_full_prompt_hit_copies_the_state_on_write(lfm2):
    """The whole prompt cached (a page multiple): the last page is copied
    on write with its ring, the re-step at L-1 reads L-2 and L-3 from the
    copy; the stream is the first run's, and a third request finds the
    shared page's ring as the first run left it."""
    model = lfm2[0]
    prompt = _ids(16, 30)
    eng = engine(model)
    first = serve(eng, [_sampled(prompt, 9)])[0]
    assert serve(eng, [_sampled(prompt, 9)])[0] == first
    assert eng.stats["cow_copies"] == 1
    assert serve(eng, [_sampled(prompt, 9)])[0] == first
    assert eng.stats["cow_copies"] == 2
    assert eng.stats["prefix_hit_admissions"] == 2


# ---- migration -------------------------------------------------------------------

def test_withdraw_then_admit_migrated_keeps_the_stream(lfm2):
    """The state travels with the blocks: a request withdrawn mid-decode and
    admitted again onto the same pages, by position, goes on as if it had
    never left."""
    model = lfm2[0]
    prompt = _ids(14, 40)
    # an eos id (one the stream never draws) makes the engine read every
    # block's tokens back inside step()
    want = serve(engine(model), [_sampled(prompt, 16, seed=3,
                                          eos_token_id=1)])[0]
    assert len(want) == 16
    eng = engine(model)
    req = _sampled(prompt, 16, seed=3, eos_token_id=1)
    eng.add_request(req)
    while len(req.output) < 6:
        eng.step()
    slot = eng.slot_of(req.rid)
    blocks, pos = list(eng._slot_blocks[slot]), int(eng._pos[slot])
    eng._alloc.incref(blocks)                 # the caller's hold on the chain
    assert eng.withdraw_active(req.rid) and not eng.has_work()
    eng.admit_migrated(req, blocks, pos, last_tok=req.output[-1])
    eng.run_until_done()
    assert req.done and list(req.output) == want


def test_what_cannot_carry_the_state_fails_by_name(lfm2):
    """The chain codec moves K and V bytes only, a speculative engine cannot
    take a draft back out of a ring, an engine without the refcounted pool
    prefills through generate()'s hook: each fails with the typed error that
    names the layer kind, none drops the state."""
    from paddle_tpu.inference.disagg import KVChainCodec

    model = lfm2[0]
    eng = engine(model)
    req = _greedy(_ids(9, 41), 12, eos_token_id=1)
    eng.add_request(req)
    while len(req.output) < 2:
        eng.step()
    with pytest.raises(LayerStateError, match="kind 'state'"):
        KVChainCodec().export_chain(eng, req.rid)
    eng.run_until_done()
    with pytest.raises(LayerStateError, match="kind 'state'"):
        engine(model, speculative=True)
    with pytest.raises(LayerStateError, match="kind 'state'"):
        engine(model, prefix_cache=None)
    with pytest.raises(ValueError, match="no int8 block format"):
        engine(model, kv_cache="int8")


def test_the_verify_hook_refuses_by_name(lfm2):
    """A verify window over state layers would lose state when a draft is
    rejected: the hook raises the typed error and runs nothing."""
    with pytest.raises(LayerStateError, match="kind 'state'"):
        lfm2[0].paged_verify_step(None, None, None)


# ---- counters ---------------------------------------------------------------------

def test_moe_counters_come_back_with_the_blocks_tokens(lfm2):
    """Rows routed, experts touched, layer steps and the fullest expert's
    rows, summed over the expert layers and token steps of the decode
    blocks; on both the eos path and the scheduled one."""
    model = lfm2[0]
    for eos in (2, None):
        eng = engine(model)
        serve(eng, [_greedy(_ids(7, 50 + i), 9, eos_token_id=eos)
                    for i in range(3)])
        st = eng.stats
        steps = st["decode_block_steps"]
        assert st["moe_layer_steps"] == 4 * steps > 0
        assert st["moe_rows_routed"] == 4 * steps * eng.max_batch * 4
        assert 4 * steps <= st["moe_experts_touched"] <= 8 * 4 * steps
        assert st["moe_rows_max_expert"] * 8 >= st["moe_rows_routed"]


# ---- models/llama: nothing moved ----------------------------------------------------

@pytest.fixture(scope="module")
def llama_streams_got_and_want():
    from _serving_streams import llama_streams

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "serving_llama_streams.json")) as f:
        return llama_streams(), json.load(f)


@pytest.mark.parametrize("kind", ["greedy", "sampled"])
def test_llama_engine_streams_equal_the_parents_byte_for_byte(
        kind, llama_streams_got_and_want):
    """Greedy streams and the cache's counters as PR 28's parent produced
    them; sampled streams as PR 29 recorded them (its sampler draws another
    random stream from the same key, so they moved once, there)."""
    got, want = llama_streams_got_and_want
    assert want["hit_tokens"] > 0 and want["cow_copies"] > 0
    assert {k: got[k] for k in ("hit_tokens", "cow_copies")} == {
        k: want[k] for k in ("hit_tokens", "cow_copies")}
    mine = [i for i in range(len(want["streams"]))
            if (i % 4 == 0) == (kind == "greedy")]     # _serving_streams' mix
    assert mine and len(got["streams"]) == len(want["streams"])
    assert [got["streams"][i] for i in mine] == [want["streams"][i]
                                                 for i in mine]


@pytest.mark.parametrize("d", [32, 128], ids=["xla_reads", "kernel_reads"])
@pytest.mark.parametrize("prefill", [False, True], ids=["decode", "prefill"])
def test_append_paged_kv_writes_each_token_once_in_both_scatter_forms(
        d, prefill):
    """``append_paged_kv`` picks its scatter by who reads the pool
    (``_kernel_takes``: rows of ``d`` a head where the paged kernel does,
    ``[heads, d]`` windows where XLA's gather does); both put token t of
    row r at ``tables[r, pos // page]``, slot ``pos % page``, and touch
    nothing else."""
    from paddle_tpu.ops.paged_attention import append_paged_kv

    rng = np.random.default_rng(3)
    heads, page, pages = 2, 16, 7
    tables = np.array([[1, 2], [3, 4], [5, 6]], np.int32)
    if prefill:
        seq = np.repeat(np.arange(3), 5).astype(np.int32)
        pos = np.tile(np.arange(14, 19), 3).astype(np.int32)   # crosses a page
    else:
        seq, pos = None, np.array([0, 17, 31], np.int32)
    n = len(pos)
    k_new, v_new = (rng.normal(size=(n, heads, d)).astype(np.float32)
                    for _ in range(2))
    k0, v0 = (rng.normal(size=(pages, heads, page, d)).astype(np.float32)
              for _ in range(2))
    k1, v1 = append_paged_kv(
        jnp.asarray(k0), jnp.asarray(v0), jnp.asarray(k_new),
        jnp.asarray(v_new), jnp.asarray(tables), jnp.asarray(pos),
        None if seq is None else jnp.asarray(seq))
    for t in range(n):
        r = t if seq is None else seq[t]
        k0[tables[r, pos[t] // page], :, pos[t] % page] = k_new[t]
        v0[tables[r, pos[t] // page], :, pos[t] % page] = v_new[t]
    np.testing.assert_array_equal(np.asarray(k1), k0)
    np.testing.assert_array_equal(np.asarray(v1), v0)
