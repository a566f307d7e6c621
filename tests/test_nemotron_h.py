"""models/nemotron_h against the plain reference
(chipbench/reference/nemotron_h.py) on seeded weights; ``ops/ssd.py`` against
the plain recurrence; the relu^2 experts' two arms; a chip's share of an
expert layer against the whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _nemotron_h_util import (TINY, engine, reference, reference_logits,
                              seeded_model, serve)
from paddle_tpu.ops import ssd


@pytest.fixture(scope="module")
def f32():
    return seeded_model(5, "float32")


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


# ---- the model against the reference -----------------------------------------

def test_forward_matches_reference_float32(f32):
    """All three mixers, float32 on both sides: what is left is the order of
    the sums (the chunked scan against the recurrence a position at a time,
    XLA's CPU dot against "highest")."""
    model, top, layer = f32
    ids = _ids(45)
    got = np.asarray(model(ids[None]))[0]
    want = reference_logits(ids, top, layer)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 1e-4


def test_forward_matches_reference_bfloat16():
    """The served type: the same bf16 weights on both sides, the program
    rounding every activation and the reference none. Read up to the first
    position whose choice of experts is a near-tie (the reference's route
    margin): there a rounding swaps an expert, the logits move by units,
    and the state and K and V carry that to every later position."""
    model, top, layer = seeded_model(11, "bfloat16")  # 20 clear positions
    ids = _ids(45)
    got = np.asarray(model(ids[None]))[0]
    ref = reference()
    x = ref.hidden_states_many(TINY, [ids[None]], layer, top)[0][0]
    want = np.asarray(ref.logits_of(TINY, x, top))
    clear = int(np.argmax(np.asarray(x[:, -1]) < 0.002))
    assert clear >= 8
    assert np.abs(got - want)[:clear].max() < 0.25
    assert np.mean(np.abs(got - want)[:clear]) < 0.03


def test_prefill_then_decode_through_the_cache_matches_reference(f32):
    """The hooks as the engine calls them: the prompt in packed chunk rows
    (the state kept short of its last token), the first-token step at the
    prompt's last position, then token steps through the cache; every
    step's logits against the reference's full forward to 1e-4."""
    model, top, layer = f32
    prompt, tail = _ids(29, 1), _ids(9, 2)
    want = reference_logits(np.concatenate([prompt, tail]), top, layer)
    page, chunk, L = 4, 8, len(prompt)
    caches = model._init_paged_caches(2, 64, page_size=page)
    table = np.asarray(caches["tables"])[1:2]              # slot 1's pages
    starts = np.arange(0, L, chunk, dtype=np.int32)
    ids = np.zeros((len(starts), chunk), np.int32)
    count = np.zeros(len(starts), np.int32)
    for r, s in enumerate(starts):
        piece = prompt[s:s + chunk]
        ids[r, :len(piece)] = piece
        count[r] = min(len(piece), L - 1 - s)
    sub = {"kv": caches["kv"], "tables": jnp.asarray(
        np.tile(table, (len(starts), 1))),
        "seq": (jnp.ones(len(starts), jnp.int32), jnp.asarray(count))}
    kv = model.paged_prefill_chunk(jnp.asarray(ids), sub,
                                   jnp.asarray(starts))["kv"]
    toks = np.concatenate([prompt[-1:], tail])
    for j, tok in enumerate(toks):
        step = {"kv": kv, "tables": jnp.asarray(table),
                "seq_slots": jnp.asarray([1], jnp.int32)}
        logits, out = model.paged_token_step(
            jnp.asarray([tok]), step, jnp.asarray([L - 1 + j], jnp.int32))
        kv = out["kv"]
        assert np.abs(np.asarray(logits[0]) - want[L - 1 + j]).max() < 1e-4


def test_engine_greedy_and_sampled_streams_match_reference(f32):
    """Through the engine, greedy and seeded-sampled rows mixed: every
    greedy token is the reference's first to 1e-3, every sampled token what
    the program's sampler draws from the REFERENCE's logits."""
    from paddle_tpu.inference.serving import Request
    from paddle_tpu.models.generation_utils import fold_keys, sample_rows

    model, top, layer = f32
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(6):
        kw = {} if i % 3 == 0 else dict(temperature=0.7, top_p=0.95,
                                        seed=50 + i)
        reqs.append(Request(_ids(int(rng.integers(6, 40)), 10 + i),
                            max_new_tokens=int(rng.integers(4, 12)), **kw))
    outs = serve(engine(model), reqs)
    for r, out in zip(reqs, outs):
        assert len(out) == r.max_new_tokens
        lg = reference_logits(np.concatenate([r.prompt, out]), top, layer)
        rows = lg[len(r.prompt) - 1: len(r.prompt) - 1 + len(out)]
        if r.temperature == 0.0:
            assert (rows.max(-1) - rows[np.arange(len(out)), out]).max() \
                < 1e-3
        else:
            n = len(out)
            keys = fold_keys(jnp.full(n, r.seed, jnp.int32),
                             jnp.arange(len(r.prompt), len(r.prompt) + n,
                                        dtype=jnp.int32))
            want = sample_rows(jnp.asarray(rows), keys,
                               jnp.full(n, r.temperature, jnp.float32),
                               jnp.full(n, r.top_p, jnp.float32),
                               jnp.zeros(n, jnp.int32))
            assert list(np.asarray(want)) == out


def test_lazy_guard_defers_and_assign_equals_eager():
    import paddle_tpu as paddle
    from chipbench.adapters import nemotron_h_block
    from chipbench.harness import weights as W
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    cfg = NemotronHConfig.tiny()
    with paddle.LazyGuard():
        lazy = NemotronHForCausalLM(cfg)
    params = [p for _, p in lazy.named_parameters()]
    assert params and all(isinstance(p._data, jax.ShapeDtypeStruct)
                          and not p.initialized for p in params)
    eager = NemotronHForCausalLM(cfg)
    # eager defaults are the family's: A in [1, 16], dt in [0.001, 0.1]
    m = eager.model.layers[0].mixer
    assert np.allclose(np.exp(np.asarray(m.A_log._data))[[0, -1]], [1, 16])
    dt = np.log1p(np.exp(np.asarray(m.dt_bias._data)))
    assert np.allclose(dt[[0, -1]], [0.001, 0.1], rtol=1e-4)
    # ... and they are what the benchmark's seeded A_log and dt_bias ride on
    ref = reference()
    a_log, dt_bias = ref.family_init(TINY)
    np.testing.assert_array_equal(np.asarray(m.A_log._data), a_log)
    np.testing.assert_array_equal(np.asarray(m.dt_bias._data), dt_bias)
    w = W.model_weights(ref.leaf_table(TINY), 9, dtype=jnp.float32)
    nemotron_h_block.assign(lazy, w)
    nemotron_h_block.assign(eager, w)
    held = ref.on_family_init(TINY, w["layers"][0])
    for leaf, got in (("A_log", m.A_log), ("dt_bias", m.dt_bias),
                      ("conv_w", m.conv_weight)):
        np.testing.assert_array_equal(np.asarray(got._data),
                                      np.asarray(held[leaf]))
    assert np.abs(np.asarray(m.A_log._data) - a_log).max() < 0.5
    # taps of 0.08: drawn at 0.1 here they stay, at 0.02 they are times 4
    assert ref.conv_scale(TINY) == 1 and ref.conv_scale(
        {"initializer_range": 0.02}) == 4
    ids = _ids(17)[None]
    np.testing.assert_array_equal(np.asarray(lazy(ids)),
                                  np.asarray(eager(ids)))


def test_scopes_reach_the_lowered_programs(f32):
    """pt.ssm and its parts, pt.moe.shared inside pt.moe, pt.attn and
    pt.lm_head are on the ops of the token step; pt.ssm.scan on the
    chunk's."""
    from paddle_tpu.jit.api import _Swap, _collect_state

    model, _, _ = f32
    caches = model._init_paged_caches(2, 16, page_size=4)
    _, tensors = _collect_state(model)
    params = [t._data for t in tensors]

    def step(params, toks, caches, pos):
        with _Swap(tensors, params):
            return model.paged_token_step(toks, caches, pos)

    def chunk(params, ids, caches, starts):
        with _Swap(tensors, params):
            return model.paged_prefill_chunk(ids, caches, starts)

    text = jax.jit(step).lower(
        params, jnp.zeros(2, jnp.int32), caches,
        jnp.array([3, 5], jnp.int32)).as_text(debug_info=True)
    for scope in ("pt.ssm/pt.ssm.in_proj", "pt.ssm/pt.ssm.conv",
                  "pt.ssm/pt.ssm.step", "pt.ssm/pt.ssm.norm",
                  "pt.ssm/pt.ssm.out_proj", "pt.moe/pt.moe.router",
                  "pt.moe/pt.moe.experts", "pt.moe/pt.moe.shared",
                  "pt.attn", "pt.lm_head", "pt.kv_write"):
        assert scope in text, scope
    text = jax.jit(chunk).lower(
        params, jnp.zeros((2, 8), jnp.int32), caches,
        jnp.zeros(2, jnp.int32)).as_text(debug_info=True)
    assert "pt.ssm/pt.ssm.scan" in text and "pt.ssm.step" not in text


# ---- ops/ssd.py against the plain recurrence -----------------------------------

H, P, G, N = 4, 8, 2, 16


def _draw(b, s, seed, dt_scale=0.7):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.3, 1.5, (b, s, H)) * dt_scale, jnp.float32)
    return dict(x=f(b, s, H, P), dt=dt, A=-jnp.asarray(
        rng.uniform(0.5, 2.0, H), jnp.float32), B=f(b, s, G, N),
        C=f(b, s, G, N), D=f(H))


def _recurrence(x, dt, A, B, C, D, init):
    """One sequence, a position at a time: (y [s, H, P], final state)."""
    state, ys = np.asarray(init, np.float64), []
    x, dt, A, B, C, D = (np.asarray(a, np.float64)
                         for a in (x, dt, A, B, C, D))
    for t in range(x.shape[0]):
        b_h, c_h = (np.repeat(a[t], H // G, axis=0) for a in (B, C))
        state = (np.exp(dt[t] * A)[:, None, None] * state
                 + (dt[t][:, None] * x[t])[:, :, None] * b_h[:, None, :])
        ys.append((state * c_h[:, None, :]).sum(-1) + D[:, None] * x[t])
    return np.stack(ys), state


@pytest.mark.parametrize("s,chunk", [(8, 8), (19, 8), (37, 8), (5, 8),
                                     (33, 16)],
                         ids=["one-chunk", "19-by-8", "37-by-8",
                              "shorter-than-a-chunk", "33-by-16"])
@pytest.mark.parametrize("with_init", [False, True],
                         ids=["from-zero", "from-a-state"])
def test_chunked_scan_equals_the_recurrence(s, chunk, with_init):
    """Across chunk edges, at lengths that are no multiple of the chunk,
    from zero and from an initial state: y and the final state."""
    a = _draw(2, s, 10 + s)
    init = (jnp.asarray(np.random.default_rng(s).normal(size=(2, H, P, N)),
                        jnp.float32) if with_init else None)
    y, final = ssd.ssd_scan(a["x"], a["dt"], a["A"], a["B"], a["C"], a["D"],
                            init=init, chunk=chunk)
    for i in range(2):
        want_y, want_s = _recurrence(
            a["x"][i], a["dt"][i], a["A"], a["B"][i], a["C"][i], a["D"],
            np.zeros((H, P, N)) if init is None else init[i])
        assert np.abs(np.asarray(y[i]) - want_y).max() < 5e-5
        assert np.abs(np.asarray(final[i]) - want_s).max() < 5e-5


def test_padded_tail_with_dt_zero_leaves_the_state():
    """Positions whose dt is 0 neither decay the state nor add to it,
    whatever their x, B and C hold."""
    a = _draw(1, 24, 3)
    dt = a["dt"].at[:, 13:].set(0.0)
    _, final = ssd.ssd_scan(a["x"], dt, a["A"], a["B"], a["C"], a["D"],
                            chunk=8)
    _, want = _recurrence(a["x"][0, :13], a["dt"][0, :13], a["A"],
                          a["B"][0, :13], a["C"][0, :13], a["D"],
                          np.zeros((H, P, N)))
    assert np.abs(np.asarray(final[0]) - want).max() < 2e-5


# a pack the engine can send, a row (slot, fresh, count of 16 positions),
# over a pool of 5 slots (slot 5: a dummy row, out of range)
_PACKS = {
    "a-run-of-one-row": [(2, False, 16)],
    "a-run-of-two-rows": [(3, True, 16), (3, False, 16)],
    "a-run-of-three-rows": [(0, False, 16), (0, False, 16), (0, False, 7)],
    "middle-row-empty": [(1, True, 16), (1, False, 0), (1, False, 16)],
    # a chunk that holds only the prompt's last token keeps none of it
    "last-row-empty": [(1, True, 16), (1, False, 16), (1, False, 0)],
    "empty-row-alone-in-its-run": [(0, False, 16), (2, True, 0),
                                   (4, False, 9)],
    "fresh-run-beside-one-that-resumes": [(1, True, 16), (1, False, 16),
                                          (3, False, 16), (3, False, 5)],
    "dummy-rows-last": [(3, True, 16), (3, False, 16), (1, False, 5),
                        (5, True, 0)],
    "dummy-rows-after-the-last-slot": [(4, False, 16), (4, False, 3),
                                       (5, True, 0), (5, True, 0)],
    # a dummy row's slot is clipped to the last one: it must not write that
    # slot's old state over what the live run left there
    "last-slot-first-dummy-rows-last": [(4, False, 16), (1, False, 16),
                                        (5, True, 0)],
    "every-slot-a-run": [(0, True, 16), (1, False, 11), (2, False, 16),
                         (3, True, 1), (4, False, 16)],
}


@pytest.mark.parametrize("chunk", [16, 8], ids=["one-chunk-a-row",
                                                "two-chunks-a-row"])
@pytest.mark.parametrize("pack", list(_PACKS), ids=list(_PACKS))
def test_pooled_scan_chains_rows_and_spares_the_rest(pack, chunk):
    """The packed chunk over a pool of 5 slots, the rows BY SEQUENCE (the
    rows of one slot adjacent, as the engine orders them): a run's first row
    starts from the slot's state, or from zero where it is fresh, each next
    row from what the one before it left, in this call, and the run's last
    state is the slot's; a row with no kept position passes on what it got,
    and a run of such rows writes nothing. ``y`` and the states against the
    recurrence a position at a time; every slot no live row names comes
    back to the bit."""
    rows = _PACKS[pack]
    a = _draw(len(rows), 16, 7)
    pool = jnp.asarray(np.random.default_rng(1).normal(size=(5, H, P, N)),
                       jnp.float32)
    slots, fresh, count = (jnp.asarray(v) for v in zip(*rows))
    count = count.astype(jnp.int32)
    dt = jnp.where(jnp.arange(16)[None, :, None] < count[:, None, None],
                   a["dt"], 0.0)
    y, new = ssd.ssd_scan_pooled(pool, a["x"], dt, a["A"], a["B"], a["C"],
                                 a["D"], slots.astype(jnp.int32), fresh,
                                 count, chunk=chunk)
    want = {}
    for i, (slot, is_fresh, n) in enumerate(rows):
        if not n:
            continue
        start = (np.zeros((H, P, N)) if is_fresh else
                 want.get(slot, np.asarray(pool[slot])))
        want_y, want[slot] = _recurrence(
            a["x"][i, :n], a["dt"][i, :n], a["A"], a["B"][i, :n],
            a["C"][i, :n], a["D"], start)
        assert np.abs(np.asarray(y[i, :n]) - want_y).max() < 5e-5
    for slot, state in want.items():
        assert np.abs(np.asarray(new[slot]) - state).max() < 5e-5
    spared = [i for i in range(5) if i not in want]
    np.testing.assert_array_equal(np.asarray(new)[spared],
                                  np.asarray(pool)[spared])


@pytest.mark.parametrize("by", ["live", "slots"])
def test_step_equals_the_recurrence_and_spares_rows_that_do_not_decode(by):
    """One token a row, by the decode block's mask or by the first-token
    program's slot ids: a row at position 0 starts from zero whatever its
    slot held, a decoding row resumes, the others' states stay to the
    bit."""
    a = _draw(3, 1, 9)
    pool = jnp.asarray(np.random.default_rng(2).normal(size=(3, H, P, N)),
                       jnp.float32)
    fresh = jnp.asarray([True, False, False])
    kw = (dict(live=jnp.asarray([True, True, False])) if by == "live" else
          dict(slots=jnp.asarray([0, 1, 3], jnp.int32)))
    y, new = ssd.ssd_step(pool, a["x"][:, 0], a["dt"][:, 0], a["A"],
                          a["B"][:, 0], a["C"][:, 0], a["D"], fresh, **kw)
    for i, init in ((0, np.zeros((H, P, N))), (1, np.asarray(pool[1]))):
        want_y, want_s = _recurrence(a["x"][i], a["dt"][i], a["A"],
                                     a["B"][i], a["C"][i], a["D"], init)
        assert np.abs(np.asarray(y[i]) - want_y[0]).max() < 1e-5
        assert np.abs(np.asarray(new[i]) - want_s).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(pool[2]))


# ---- the expert layer ---------------------------------------------------------------

def _expert_layer(first=0, count=8, seed=0):
    from paddle_tpu.incubate.distributed.models.moe import (
        DroplessMoE, Relu2ExpertFFN, SigmoidGate)
    from paddle_tpu.models.nemotron_h.modeling import (NemotronHConfig,
                                                       NemotronHMLP)

    cfg = NemotronHConfig.tiny()
    w = np.random.default_rng(seed)
    layer = DroplessMoE(
        64, 8, 32, gate=SigmoidGate(64, 8, topk=3, scaling=2.5,
                                    norm_eps=1e-20, initializer_range=0.1),
        first=first, count=count,
        experts=Relu2ExpertFFN(count, 64, 32), shared=NemotronHMLP(cfg, 48))
    draws = {"router": w.normal(0, 0.1, (64, 8)), "bias": w.normal(0, 0.1, 8),
             "up": w.normal(0, 0.1, (8, 64, 32)),
             "down": w.normal(0, 0.1, (8, 32, 64)),
             "s_up": w.normal(0, 0.1, (64, 48)),
             "s_down": w.normal(0, 0.1, (48, 64))}
    f = lambda a: jnp.asarray(a, jnp.float32)
    layer.gate.gate_weight._data = f(draws["router"])
    layer.gate.expert_bias._data = f(draws["bias"])
    layer.experts.w_up._data = f(draws["up"][first:first + count])
    layer.experts.w_down._data = f(draws["down"][first:first + count])
    layer.shared.up_proj_weight._data = f(draws["s_up"])
    layer.shared.down_proj_weight._data = f(draws["s_down"])
    return layer, {k: f(v) for k, v in draws.items()}


def test_the_shares_routed_parts_plus_the_shared_expert_once_are_the_layer():
    """Eight shares of one expert each: their outputs, each less the shared
    expert (every chip computes it alike), summed, plus the shared expert
    once, equal the uncut layer, and that equals the reference's uncut
    layer."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(21, 64)),
                    jnp.float32)
    whole, w = _expert_layer(0, 8)
    shared = whole.shared(x)
    parts = sum(_expert_layer(e, 1)[0](x) - shared for e in range(8))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole(x)), atol=2e-5)
    ref = reference()
    rw = {"router": w["router"], "e_score_correction_bias": w["bias"],
          "experts_up": w["up"], "experts_down": w["down"],
          "shared_up": w["s_up"], "shared_down": w["s_down"]}
    with jax.default_matmul_precision("highest"):
        idx, g, _ = ref.route(rw, x, k=3, renorm=True, scaling=2.5, first=0,
                              held=8)
        want = ref.experts_op(rw, x, idx, g, first=0) + ref.shared_op(rw, x)
        # and the reference's own shares add up the same way
        cut = lambda e: dict(rw, experts_up=rw["experts_up"][e:e + 1],
                             experts_down=rw["experts_down"][e:e + 1])
        ref_parts = sum(ref.experts_op(cut(e), x, idx, g, first=e)
                        for e in range(8))
    np.testing.assert_allclose(np.asarray(whole(x)), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref_parts + ref.shared_op(rw, x)),
                               np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("first,count", [(0, 8), (2, 3)],
                         ids=["all-experts", "a-share"])
def test_relu2_dense_and_sorted_arms_agree(first, count, monkeypatch):
    """Both arms of dropless_ffn on two-matrix relu^2 experts, and the rows
    each local expert got."""
    from paddle_tpu.incubate.distributed.models.moe import moe_layer

    layer, _ = _expert_layer(first, count)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)),
                    jnp.float32)
    idx, gates = layer.gate.route(x)
    dense, rows_d = moe_layer.dropless_ffn(x, idx, gates, layer.experts,
                                           first)
    monkeypatch.setattr(moe_layer, "_DENSE_ROWS", 0)
    assert moe_layer.dropless_arm(40) == "sorted"
    sorted_, rows_s = moe_layer.dropless_ffn(x, idx, gates, layer.experts,
                                             first)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(sorted_),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(rows_d), np.asarray(rows_s))
    want = [(np.asarray(idx) == first + e).sum() for e in range(count)]
    assert list(np.asarray(rows_d)) == want
