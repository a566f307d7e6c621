"""State kept a sequence (docs/SERVING.md "State that is not pages", kind
"seq"): one test a line of the contract between the serving engine and a
model whose layers keep an ``ops.paged_attention.SeqState``, on
``models/nemotron_h`` at the tiny size in float32. ``A_log`` and ``dt_bias``
are set by hand so that a state lasts hundreds of positions: with the seeded
ones it forgets within tens, and a state lost at a chunk edge, at a slot's
reuse or under a row that does not decode would pass unseen."""

import jax.numpy as jnp
import numpy as np
import pytest

from _nemotron_h_util import (engine, reference_logits, seeded_model, serve)
from paddle_tpu.inference.serving import (LayerStateError, PackOrderError,
                                          PrefixCacheConfig, Request)
from paddle_tpu.ops.paged_attention import SeqState, layer_kinds

PAGE = 4


@pytest.fixture(scope="module")
def nemo():
    return seeded_model(5, "float32", max_positions=256, long_memory=True)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


def _greedy(prompt, n=8, **kw):
    return Request(np.asarray(prompt, np.int32), max_new_tokens=n, **kw)


def _sampled(prompt, n=8, seed=11, **kw):
    return Request(np.asarray(prompt, np.int32), max_new_tokens=n,
                   temperature=0.7, top_p=0.95, seed=seed, **kw)


def _big(model, **kw):
    args = dict(max_len=256, prefix_cache=PrefixCacheConfig(extra_blocks=8))
    args.update(kw)
    return engine(model, **args)


def _is_the_references(prompt, out, top, layer, tol=1e-3):
    """Every served token is the reference's first at its position, over
    the whole sequence from its start."""
    lg = reference_logits(np.concatenate([prompt, out]), top, layer)
    rows = lg[len(prompt) - 1: len(prompt) - 1 + len(out)]
    gap = rows.max(-1) - rows[np.arange(len(out)), out]
    assert gap.max() < tol, gap


# ---- the model says what its layers keep ---------------------------------------

def test_model_states_what_each_layer_keeps(nemo):
    model = nemo[0]
    caches = model._init_paged_caches(3, 16, page_size=PAGE)
    # MEM*EME: the expert layers keep nothing and have no entry
    assert layer_kinds(caches["kv"]) == ["seq", "seq", "kv", "seq"]
    st = caches["kv"][0]
    assert isinstance(st, SeqState)
    assert st.ssm.shape == (3, 8, 8, 16) and st.ssm.dtype == jnp.float32
    assert st.conv.shape == (3, 3, 64 + 2 * 2 * 16)
    eng = engine(model)
    assert eng._seq_layers == [0, 1, 3] and eng._state_layers == []
    assert eng.stats["seq_state_bytes"] == 3 * 4 * (
        8 * 8 * 16 * 4 + 3 * 128 * 4)


def test_the_hand_set_state_lasts_hundreds_of_positions(nemo):
    """What makes the tests below see a lost state: the last position's
    logits depend on tokens 150 positions back by far more than any
    tolerance used here."""
    _, top, layer = nemo
    ids = _ids(200, 1)
    whole = reference_logits(ids, top, layer)[-1]
    tail = reference_logits(ids[150:], top, layer)[-1]
    other = reference_logits(np.concatenate([_ids(150, 2), ids[150:]]),
                             top, layer)[-1]
    assert np.abs(whole - tail).max() > 0.05
    assert np.abs(whole - other).max() > 0.05


# ---- chunked and packed prefill ----------------------------------------------------

def _prefill(model, prompt, chunk, slot=1, pad_id=0, kv=None):
    """``prompt`` through ``paged_prefill_chunk`` as the engine's pack does:
    a row a chunk of ``chunk`` tokens, in order, in one call, each row's
    state kept short of the prompt's last token; then the first-token step.
    Returns (its logits, the caches' kv)."""
    L = len(prompt)
    caches = model._init_paged_caches(3, 256, page_size=PAGE)
    table = np.asarray(caches["tables"])[slot:slot + 1]
    starts = np.arange(0, L, chunk, dtype=np.int32)
    ids = np.full((len(starts), chunk), pad_id, np.int32)
    count = np.zeros(len(starts), np.int32)
    for r, s in enumerate(starts):
        piece = prompt[s:s + chunk]
        ids[r, :len(piece)] = piece
        count[r] = min(len(piece), L - 1 - s)
    sub = {"kv": caches["kv"] if kv is None else kv,
           "tables": jnp.asarray(np.tile(table, (len(starts), 1))),
           "seq": (jnp.full(len(starts), slot, jnp.int32),
                   jnp.asarray(count))}
    kv = model.paged_prefill_chunk(jnp.asarray(ids), sub,
                                   jnp.asarray(starts))["kv"]
    logits, out = model.paged_token_step(
        jnp.asarray(prompt[-1:]),
        {"kv": kv, "tables": jnp.asarray(table),
         "seq_slots": jnp.asarray([slot], jnp.int32)},
        jnp.asarray([L - 1], jnp.int32))
    return np.asarray(logits[0]), out["kv"]


@pytest.mark.parametrize("chunk", [8, 16, 40, 168])
def test_a_prompt_split_over_chunks_of_any_size_equals_the_reference(nemo,
                                                                      chunk):
    """161 tokens in rows of 8, 16, 40 or one of 168: the rows of one
    sequence chain inside the program (each resumes from what the row
    before left in the slot), and the logits at the prompt's end are the
    reference's, which saw the whole sequence a position at a time."""
    model, top, layer = nemo
    prompt = _ids(161, 5)
    want = reference_logits(prompt, top, layer)[-1]
    got, kv = _prefill(model, prompt, chunk)
    assert np.abs(got - want).max() < 1e-4
    if chunk != 168:
        one, kv_one = _prefill(model, prompt, 168)
        for a, b in zip(kv, kv_one):
            if isinstance(a, SeqState):
                np.testing.assert_allclose(a.ssm[1], b.ssm[1], atol=2e-5)
                np.testing.assert_allclose(a.conv[1], b.conv[1], atol=1e-6)


def test_padded_tail_and_other_slots_leave_no_trace(nemo):
    """Whatever ids pad the last chunk, the slot's state and the logits come
    out the same to the bit; the other slots' rows stay as they were."""
    model = nemo[0]
    prompt = _ids(37, 6)
    a, kva = _prefill(model, prompt, 8, pad_id=0)
    b, kvb = _prefill(model, prompt, 8, pad_id=77)
    np.testing.assert_array_equal(a, b)
    for ea, eb in zip(kva, kvb):
        if isinstance(ea, SeqState):
            np.testing.assert_array_equal(np.asarray(ea.ssm),
                                          np.asarray(eb.ssm))
            np.testing.assert_array_equal(np.asarray(ea.conv),
                                          np.asarray(eb.conv))
            assert not np.asarray(ea.ssm)[[0, 2]].any()
            assert not np.asarray(ea.conv)[[0, 2]].any()


def test_a_chunk_row_at_position_zero_starts_from_zero(nemo):
    """No reset program: a slot that holds another sequence's state gives a
    new prompt the logits of a fresh slot, because the row that starts at
    position 0 starts from zero."""
    model = nemo[0]
    _, kv = _prefill(model, _ids(50, 7), 16)
    fresh, _ = _prefill(model, _ids(33, 8), 16)
    reused, _ = _prefill(model, _ids(33, 8), 16, kv=kv)
    np.testing.assert_allclose(reused, fresh, atol=1e-6)


# ---- through the engine ----------------------------------------------------------------

@pytest.mark.parametrize("chunk", [None, 16])
def test_long_prompts_over_several_ticks_stream_the_references(nemo, chunk):
    """Prompts of 150 and 97 tokens in chunks of 32 (or 16): several rows a
    prompt a pack, several packs a prompt; the greedy streams are the
    reference's."""
    model, top, layer = nemo
    reqs = [_greedy(_ids(150, 20), 6), _greedy(_ids(97, 21), 9)]
    eng = _big(model, prefix_cache=PrefixCacheConfig(
        extra_blocks=8, prefill_chunk=chunk, pack_rows=3))
    for r, out in zip(reqs, serve(eng, reqs)):
        _is_the_references(r.prompt, out, top, layer)
    assert eng.stats["seq_state_starts"] == 2


def _packs_seen(eng):
    """Spy on the packed chunk's calls: a list a call of (starts [g], the
    rows' slots [g] or None for a model without "seq" layers)."""
    seen, call = [], eng._call_built

    def spy(name, key, fn, *args, **kw):
        if name == "pt_prefill_chunk":
            # (params, ids, kv, rows, starts, ..., the rows' slots, kept)
            seen.append((np.asarray(args[4]).tolist(),
                         np.asarray(args[-2]).tolist()
                         if eng._seq_layers else None))
        return call(name, key, fn, *args, **kw)

    eng._call_built = spy
    return seen


_ONE_PACK = PrefixCacheConfig(extra_blocks=8, prefill_chunk=32, pack_rows=5)


def test_one_packs_rows_reach_the_model_by_sequence(nemo):
    """Prompts of three and of two chunks of 32 prefilled in ONE pack of five
    rows: picked breadth-first, handed over by (slot, offset), the three
    dummy rows of the bucket of 8 last; two runs, a state gathered for each;
    the greedy streams are the reference's."""
    model, top, layer = nemo
    reqs = [_greedy(_ids(80, 30), 6), _greedy(_ids(50, 31), 6)]
    eng = _big(model, prefix_cache=_ONE_PACK)
    seen = _packs_seen(eng)
    for r, out in zip(reqs, serve(eng, reqs)):
        _is_the_references(r.prompt, out, top, layer)
    assert seen == [([0, 32, 64, 0, 32, 0, 0, 0],
                     [0, 0, 0, 1, 1] + [eng.max_batch] * 3)]
    assert eng.stats["packed_rows"] == 5
    assert eng.stats["seq_state_runs"] == 2
    assert eng.stats["seq_state_starts"] == 2


def test_runs_count_a_sequence_once_a_pack_over_several_packs(nemo):
    """A budget of three rows a pack: the prompts of three and two chunks
    take two packs, (slot 0 x 2, slot 1) then (slot 0, slot 1): four runs for
    five rows, each pack by sequence."""
    model, top, layer = nemo
    reqs = [_greedy(_ids(80, 30), 6), _greedy(_ids(50, 31), 6)]
    eng = _big(model, prefix_cache=PrefixCacheConfig(
        extra_blocks=8, prefill_chunk=32, pack_rows=3))
    seen = _packs_seen(eng)
    for r, out in zip(reqs, serve(eng, reqs)):
        _is_the_references(r.prompt, out, top, layer)
    assert seen == [([0, 32, 0, 0], [0, 0, 1, eng.max_batch]),
                    ([64, 32], [0, 1])]
    assert eng.stats["packed_rows"] == 5
    assert eng.stats["seq_state_runs"] == 4


def test_a_model_without_such_layers_gets_the_rows_breadth_first():
    """The same two prompts on a llama: no layer of kind "seq", so the pack
    keeps the order it was picked in, one chunk a slot a pass, and counts no
    run."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    eng = engine(LlamaForCausalLM(cfg), max_len=128, prefix_cache=_ONE_PACK)
    seen = _packs_seen(eng)
    out = serve(eng, [_greedy(_ids(80, 30), 4), _greedy(_ids(50, 31), 4)])
    assert all(len(o) == 4 for o in out)
    assert seen == [([0, 0, 32, 32, 64, 0, 0, 0], None)]
    assert eng.stats["packed_rows"] == 5
    assert eng.stats["seq_state_runs"] == 0


@pytest.mark.parametrize("rows,at", [
    ([(0, 0), (1, 0), (0, 32)], "slot 0"),       # the breadth-first pick
    ([(2, 32), (2, 0)], "slot 2"), ([(2, 0), (2, 64)], "slot 2")],
    ids=["a-slot-in-two-runs", "a-run-that-falls", "a-run-with-a-gap"])
def test_rows_out_of_order_fail_by_name(nemo, rows, at):
    """The engine checks on the host, before the call, what the op cannot
    raise on: a slot's rows adjacent, each where the one before it ended."""
    eng = _big(nemo[0], prefix_cache=_ONE_PACK)
    req = _greedy(_ids(80, 30), 6)
    with pytest.raises(PackOrderError, match=f"PT-SRV-011.*{at}.*adjacent "
                                             f"and rising"):
        eng._seq_runs([(slot, req, off) for slot, off in rows])


def test_a_slot_reused_by_a_second_request_starts_afresh(nemo):
    """One slot: the second request, on the first one's slot, streams what
    it streams on a fresh engine, and that is the reference's."""
    model, top, layer = nemo
    a, b = _ids(120, 22), _ids(41, 23)
    fresh = serve(_big(model, max_batch=1), [_greedy(b, 12)])[0]
    eng = _big(model, max_batch=1)
    serve(eng, [_greedy(a, 10)])
    assert serve(eng, [_greedy(b, 12)])[0] == fresh
    _is_the_references(b, fresh, top, layer)
    assert eng.stats["seq_state_starts"] == 2


def test_a_mid_prefill_slot_keeps_its_state_while_others_decode(nemo):
    """A 170-token prompt admitted while another request decodes: for
    several ticks its slot is mid-prefill, a row of the decode block that
    does not decode; the block leaves its state alone, and both streams are
    the reference's."""
    model, top, layer = nemo
    cfg = PrefixCacheConfig(extra_blocks=8, prefill_chunk=16, pack_rows=1)
    a, b = _ids(30, 24), _ids(170, 25)
    alone = serve(_big(model, prefix_cache=cfg),
                  [_greedy(a, 40), _greedy(b, 8)])
    # an eos id neither stream draws makes the engine read every block's
    # tokens back inside step()
    eos = next(t for t in range(3, 512) if t not in alone[0] + alone[1])
    eng = _big(model, prefix_cache=cfg)
    first = _greedy(a, 40, eos_token_id=eos)
    eng.add_request(first)
    while len(first.output) < 3:
        eng.step()
    late = _greedy(b, 8, eos_token_id=eos)
    eng.add_request(late)
    blocks = eng.stats["decode_blocks"]
    for _ in range(5):
        eng.step()
    assert eng.slot_of(late.rid) in eng._prefill_next
    assert eng.stats["decode_blocks"] == blocks + 5 and not first.done
    eng.run_until_done()
    assert [list(first.output), list(late.output)] == alone
    _is_the_references(a, alone[0], top, layer)
    _is_the_references(b, alone[1], top, layer)


def test_a_full_batch_of_different_ages_streams_the_references(nemo):
    """Four slots, seven requests of different lengths that start, finish
    and hand their slots on around each other, greedy and sampled mixed:
    every greedy stream is the reference's; a request's stream is the same
    alone."""
    model, top, layer = nemo
    reqs = [_greedy(_ids(90, 30), 5), _sampled(_ids(14, 31), 17),
            _greedy(_ids(45, 32), 21), _greedy(_ids(7, 33), 3),
            _sampled(_ids(120, 34), 9), _greedy(_ids(64, 35), 12),
            _greedy(_ids(33, 36), 26)]
    outs = serve(_big(model), reqs)
    for r, out in zip(reqs, outs):
        if r.temperature == 0.0:
            _is_the_references(r.prompt, out, top, layer)
    alone = serve(_big(model), [_sampled(_ids(120, 34), 9)])[0]
    assert outs[4] == alone


# ---- what does not carry the state -----------------------------------------------------

def test_the_radix_trie_is_neither_asked_nor_fed(nemo):
    """Two requests with one prompt: no hit, nothing registered, a counter
    for the admissions it applied to; the streams are equal."""
    model = nemo[0]
    eng = _big(model)
    prompt = _ids(40, 40)
    a = serve(eng, [_greedy(prompt, 6)])[0]
    b = serve(eng, [_greedy(prompt, 6)])[0]
    assert a == b
    assert eng.stats["hit_tokens"] == 0 and len(eng._radix) == 0
    assert eng.stats["prefix_hit_admissions"] == 0
    assert eng.stats["prefix_declined_admissions"] == 2
    assert eng.stats["miss_tokens"] == 80


def test_what_cannot_carry_the_state_fails_by_name(nemo):
    """The chain codec moves K and V bytes only, a migrated chain lands in
    another slot, a speculative engine cannot take a draft back, an engine
    without the refcounted pool prefills through generate()'s hook: each
    fails with the typed error that names the kind, none drops the state."""
    from paddle_tpu.inference.disagg import KVChainCodec

    model = nemo[0]
    eng = engine(model)
    req = _greedy(_ids(9, 41), 12, eos_token_id=1)
    eng.add_request(req)
    while len(req.output) < 2:
        eng.step()
    with pytest.raises(LayerStateError, match="kind 'seq'"):
        KVChainCodec().export_chain(eng, req.rid)
    slot = eng.slot_of(req.rid)
    blocks, pos = list(eng._slot_blocks[slot]), int(eng._pos[slot])
    with pytest.raises(LayerStateError, match="kind 'seq'"):
        eng.admit_migrated(req, blocks, pos, last_tok=req.output[-1])
    eng.run_until_done()
    assert req.done and len(req.output) == 12
    with pytest.raises(LayerStateError, match="kind 'seq'"):
        engine(model, speculative=True)
    with pytest.raises(LayerStateError, match="kind 'seq'"):
        engine(model, prefix_cache=None)
    with pytest.raises(ValueError, match="no int8 block format"):
        engine(model, kv_cache="int8")
    with pytest.raises(LayerStateError, match="kind 'seq'"):
        model.paged_verify_step(None, None, None)


# ---- counters --------------------------------------------------------------------------

def test_picks_and_local_rows_come_back_with_the_blocks_tokens():
    """A model that holds experts [0, 3) of 8: ``moe_picks`` counts every
    pick the routers made in the decode blocks (rows x 3 x expert layers x
    token steps), ``moe_rows_routed`` those that went to a held expert."""
    from chipbench.adapters import nemotron_h_block
    from chipbench.harness import weights as W
    from _nemotron_h_util import TINY, reference

    cfg = dict(TINY, n_routed_experts=3, published={"n_routed_experts": 8})
    model = nemotron_h_block.build_model(cfg, max_positions=64,
                                         dtype="float32")
    assert model.model.layers[1].mixer.experts.w_up._data.shape[0] == 3
    assert model.model.layers[1].mixer.gate.gate_weight._data.shape[1] == 8
    nemotron_h_block.assign(model, W.model_weights(
        reference().leaf_table(cfg), 3, dtype=jnp.float32))
    eng = engine(model)
    serve(eng, [_greedy(_ids(10, 50), 9, eos_token_id=1),
                _sampled(_ids(6, 51), 5, eos_token_id=1)])
    st = eng.stats
    assert st["moe_layer_steps"] == 3 * st["decode_block_steps"]
    assert st["moe_picks"] == 4 * 3 * st["moe_layer_steps"]
    assert 0 < st["moe_rows_routed"] < st["moe_picks"]
