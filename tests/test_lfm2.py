"""models/lfm2 against the plain reference (chipbench/reference/lfm2.py) on
seeded weights: the forward, prefill then decode through the serving engine,
the router's choice, and deferred initialisation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _lfm2_util import (TINY, engine, reference, reference_logits,
                        seeded_model, serve)


@pytest.fixture(scope="module")
def f32():
    return seeded_model(5, "float32")


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


def test_forward_matches_reference_float32(f32):
    """Float32 weights and arithmetic on both sides: what is left is the
    order of the sums (the reference sorts the routed pairs, the program
    multiplies every expert by its gate; XLA's CPU dot against
    "highest"): 1e-4 on logits of a few units."""
    model, top, layer = f32
    ids = _ids(40)
    got = np.asarray(model(ids[None]))[0]
    want = reference_logits(ids, top, layer)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 1e-4


def test_forward_matches_reference_bfloat16():
    """The served type: weights are the same bf16 values on both sides, the
    program rounds every activation to bf16 (8 bits of mantissa: 4e-3
    relative a rounding, a few dozen of them in five layers), the reference
    none. Logits of a few units agree to 0.15; a token whose fourth and
    fifth expert swap under that rounding moves no further."""
    model, top, layer = seeded_model(5, "bfloat16")
    ids = _ids(40)
    got = np.asarray(model(ids[None]))[0]
    want = reference_logits(ids, top, layer)
    assert np.abs(got - want).max() < 0.15
    assert np.mean(np.abs(got - want)) < 0.02


def test_engine_prefill_then_decode_matches_reference(f32):
    """Greedy and seeded-sampled rows mixed, float32 model: every greedy
    token is the reference's first to 1e-3, every sampled token is what the
    program's sampler draws from the REFERENCE's logits at that position
    with the request's seed."""
    from paddle_tpu.inference.serving import Request
    from paddle_tpu.models.generation_utils import fold_keys, sample_rows

    model, top, layer = f32
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(7):
        kw = {} if i % 3 == 0 else dict(temperature=0.7, top_p=0.95,
                                        seed=50 + i)
        reqs.append(Request(_ids(int(rng.integers(6, 30)), 10 + i),
                            max_new_tokens=int(rng.integers(4, 12)), **kw))
    outs = serve(engine(model), reqs)
    for r, out in zip(reqs, outs):
        assert len(out) == r.max_new_tokens
        lg = reference_logits(np.concatenate([r.prompt, out]), top, layer)
        rows = lg[len(r.prompt) - 1: len(r.prompt) - 1 + len(out)]
        if r.temperature == 0.0:
            gap = rows.max(-1) - rows[np.arange(len(out)), out]
            assert gap.max() < 1e-3
        else:
            n = len(out)
            keys = fold_keys(jnp.full(n, r.seed, jnp.int32),
                             jnp.arange(len(r.prompt),
                                        len(r.prompt) + n, dtype=jnp.int32))
            want = sample_rows(jnp.asarray(rows), keys,
                               jnp.full(n, r.temperature, jnp.float32),
                               jnp.full(n, r.top_p, jnp.float32),
                               jnp.zeros(n, jnp.int32))
            assert list(np.asarray(want)) == out


def test_router_picks_what_the_reference_picks(f32):
    """Tie-safe: on the seeded weights the program's float32 router and the
    reference choose the same experts for every token, except where the
    reference's own margin between its last chosen and first unchosen
    ``s + b`` is a float32 rounding (1e-6)."""
    model, top, layer = f32
    ref = reference()
    w = layer(2)
    m = np.random.default_rng(7).standard_normal((200, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, want_g, margin = ref.route(w, jnp.asarray(m), k=4, renorm=True,
                                         scaling=1.0)
        s = jax.nn.sigmoid(jnp.asarray(m) @ w["router"]) + w["expert_bias"]
    got, got_g = model.model.layers[2].feed_forward.gate.route(m)
    assert np.abs(np.asarray(w["expert_bias"])).max() > 0
    top5 = np.sort(np.asarray(s), -1)[:, ::-1][:, :5]
    # the margin the reference states is the gap between its 4th and 5th
    np.testing.assert_allclose(np.asarray(margin), top5[:, 3] - top5[:, 4],
                               atol=1e-6)
    margin = np.asarray(margin)
    same = np.sort(np.asarray(got), -1) == np.sort(np.asarray(want), -1)
    assert np.all(same.all(-1) | (margin < 1e-6))
    assert same.all(-1).mean() > 0.99
    rows = same.all(-1)
    order_g = np.argsort(np.asarray(got), -1)
    order_w = np.argsort(np.asarray(want), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(got_g), order_g, -1)[rows],
        np.take_along_axis(np.asarray(want_g), order_w, -1)[rows],
        atol=1e-6)


def test_lazy_guard_defers_and_assign_equals_eager():
    """A model built under LazyGuard owns no device buffer; after the same
    assignment it equals one built eagerly and assigned."""
    import paddle_tpu as paddle
    from chipbench.adapters import lfm2_block
    from chipbench.harness import weights as W
    from paddle_tpu.models.lfm2 import Lfm2Config, Lfm2ForCausalLM

    cfg = Lfm2Config.tiny()
    with paddle.LazyGuard():
        assert paddle.LazyGuard.active()
        lazy = Lfm2ForCausalLM(cfg)
    assert not paddle.LazyGuard.active()
    params = [p for _, p in lazy.named_parameters()]
    assert params and all(isinstance(p._data, jax.ShapeDtypeStruct)
                          and not p.initialized for p in params)
    assert all(p.logical_axes == q.logical_axes for p, q in zip(
        params, (q for _, q in Lfm2ForCausalLM(cfg).named_parameters()))
        if hasattr(q, "logical_axes"))
    eager = Lfm2ForCausalLM(cfg)
    assert all(isinstance(p._data, jax.Array) and p.initialized
               for _, p in eager.named_parameters())
    w = W.model_weights(reference().leaf_table(TINY), 9, dtype=jnp.float32)
    lfm2_block.assign(lazy, w)
    lfm2_block.assign(eager, w)
    assert all(p.initialized for p in params)
    ids = _ids(17)[None]
    np.testing.assert_array_equal(np.asarray(lazy(ids)),
                                  np.asarray(eager(ids)))


def test_scopes_reach_the_lowered_decode_step(f32):
    """pt.conv, pt.attn, pt.moe (router and experts inside it), pt.mlp,
    pt.lm_head, pt.state_write and pt.kv_write are on the ops of the token
    step."""
    from paddle_tpu.jit.api import _Swap, _collect_state

    model, _, _ = f32
    caches = model._init_paged_caches(2, 16, page_size=4)
    _, tensors = _collect_state(model)

    def step(params, toks, caches, pos):
        with _Swap(tensors, params):
            return model.paged_token_step(toks, caches, pos)

    text = jax.jit(step).lower(
        [t._data for t in tensors], jnp.zeros(2, jnp.int32), caches,
        jnp.array([3, 5], jnp.int32)).as_text(debug_info=True)
    for scope in ("pt.conv", "pt.attn", "pt.moe/pt.moe.router",
                  "pt.moe/pt.moe.experts", "pt.mlp", "pt.lm_head",
                  "pt.state_write", "pt.kv_write"):
        assert scope in text, scope
