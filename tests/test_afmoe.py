"""models/afmoe against the plain reference (chipbench/reference/afmoe.py)
on seeded weights: logits, prefill then decode through the cache past two
windows, the parts that are new (rotary by kind, the output gate, the four
norms, the embedding's scale), and a chip's share of an expert layer against
the whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _afmoe_util import (TINY, reference, reference_logits, seeded_model)


@pytest.fixture(scope="module")
def f32():
    return seeded_model(5, "float32")


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 512, n).astype(np.int32)


def test_forward_matches_reference_float32(f32):
    """Both attention kinds, dense and expert layers, 70 positions (past
    four windows of 16), float32 on both sides: what is left is the order
    of the sums."""
    model, top, layer = f32
    ids = _ids(70)
    got = np.asarray(model(ids[None]))[0]
    want = reference_logits(ids, top, layer)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 2e-4


def test_forward_matches_reference_bfloat16():
    """The served type: the same bf16 weights on both sides, the program
    rounding every activation and the reference none. Where a position's
    choice of experts is a near-tie a rounding swaps an expert and the
    logits move by up to a unit (and K and V carry that on), so the typical
    position is held tightly and the worst loosely; float32 above is the
    strict comparison."""
    model, top, layer = seeded_model(11, "bfloat16")
    ids = _ids(45)
    got = np.asarray(model(ids[None]))[0]
    want = reference_logits(ids, top, layer)
    worst = np.abs(got - want).max(axis=1)             # a position
    assert np.abs(want).max() > 2.0
    assert np.median(worst) < 0.12
    assert np.mean(np.abs(got - want)) < 0.05
    assert worst.max() < 2.5


@pytest.mark.parametrize("group_blocks", [None, (48, 20)])
def test_prefill_then_decode_through_the_cache_matches_reference(
        f32, group_blocks):
    """The hooks as the engine calls them: the prompt in packed chunk rows,
    the first-token step at the prompt's last position, then token steps
    through the cache to 70 positions; every step's logits against the
    reference's full forward. With ``group_blocks`` the window layers' pool
    is smaller than the sequence and their table maps only what the window
    and the chunk in flight need, the entries behind it left stale."""
    model, top, layer = f32
    prompt, tail = _ids(45, 1), _ids(25, 2)
    want = reference_logits(np.concatenate([prompt, tail]), top, layer)
    page, chunk, L, W = 4, 8, len(prompt), TINY["sliding_window"]
    caches = model._init_paged_caches(1, 128, page_size=page, num_blocks=48,
                                      group_blocks=group_blocks)
    maxp = 32
    full = np.arange(maxp, dtype=np.int32)
    if group_blocks is None:
        tables = lambda pos: jnp.asarray(full[None])
    else:
        ring = group_blocks[1] - 1      # pages of the window pool, as a ring

        def tables(pos):
            """The window table as the engine leaves it before a program at
            ``pos``: pages from the window's first to the chunk's last
            mapped, everything else stale (page 19: never read)."""
            w = np.full(maxp, 19, np.int32)
            lo = max(0, pos - W + 1) // page
            hi = min(maxp, -(-(pos + chunk) // page))
            w[lo:hi] = np.arange(lo, hi) % ring
            return (jnp.asarray(full[None]), jnp.asarray(w[None]))

    kv = caches["kv"]
    for s in range(0, L, chunk):                # one chunk row a call
        piece = np.zeros(chunk, np.int32)
        piece[:len(prompt[s:s + chunk])] = prompt[s:s + chunk]
        kv = model.paged_prefill_chunk(
            jnp.asarray(piece[None]), {"kv": kv, "tables": tables(s)},
            jnp.asarray([s], jnp.int32))["kv"]
    toks = np.concatenate([prompt[-1:], tail])
    for i, t in enumerate(toks[:-1]):
        pos = L - 1 + i
        logits, out = model.paged_token_step(
            jnp.asarray([t]), {"kv": kv, "tables": tables(pos)},
            jnp.asarray([pos], jnp.int32))
        kv = out["kv"]
        assert np.abs(np.asarray(logits)[0] - want[pos]).max() < 3e-4, pos
    assert out["counters"]["moe_rows"].shape == (4, 8)


def test_the_window_and_the_rotary_are_by_kind(f32):
    """A sliding layer forgets what lies behind its window and turns q and
    k; a full layer does neither: moving the first token of a 40-position
    input changes a sliding layer's output only inside the window, a full
    layer's everywhere after; and shifting all positions by one changes a
    sliding layer's attention (through rotary only by a rounding: relative)
    and leaves a full layer's exactly."""
    model, _, _ = f32
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 40, 64)),
                    jnp.float32)
    x2 = x.at[0, 0].add(1.0)
    sliding = model.model.layers[0].self_attn
    full = model.model.layers[3].self_attn
    assert sliding.window == 16 and full.window is None
    d_s = np.abs(np.asarray(sliding(x2) - sliding(x)))[0].max(-1)
    d_f = np.abs(np.asarray(full(x2) - full(x)))[0].max(-1)
    assert d_s[:16].min() > 0 and d_s[16:].max() == 0
    assert d_f.min() > 0
    # no rotary on the full layer: its q and k do not see the position
    q0, k0, _ = full._qkv(x, jnp.zeros((1, 40), jnp.int32))
    q1, k1, _ = full._qkv(x, jnp.full((1, 40), 7, jnp.int32))
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    qs0, _, _ = sliding._qkv(x, jnp.zeros((1, 40), jnp.int32))
    qs1, _, _ = sliding._qkv(x, jnp.full((1, 40), 7, jnp.int32))
    assert np.abs(np.asarray(qs0 - qs1)).max() > 1e-3


def test_the_parts_a_layer_is_made_of(f32):
    """The output gate, the sandwich norms and the embedding's scale, each
    against the reference's own function on the same leaves."""
    model, top, layer = f32
    ref = reference()
    ids = _ids(33, 7)
    emb = np.asarray(model.model.embed(jnp.asarray(ids)))
    np.testing.assert_allclose(
        emb, np.asarray(top["embed"])[ids] * np.sqrt(64.0), rtol=1e-6)
    w = layer(3)                                   # the full layer
    a = jnp.asarray(np.random.default_rng(8).normal(size=(33, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_op(w, a, n_heads=4, n_kv=2, eps=1e-5,
                                theta=1e4, window=None, rotary=False)
        want_w = ref.attention_op(layer(0), a, n_heads=4, n_kv=2, eps=1e-5,
                                  theta=1e4, window=16, rotary=True)
    got = model.model.layers[3].self_attn(a[None])[0]
    got_w = model.model.layers[0].self_attn(a[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               atol=2e-5)
    # a gate of zero weights halves the output: sigmoid(0)
    attn = model.model.layers[3].self_attn
    keep = attn.gate_proj_weight._data
    try:
        o = attn._out(a[None], jnp.ones((1, 33, 64), jnp.float32))
        attn.gate_proj_weight._data = jnp.zeros_like(keep)
        half = attn._out(a[None], jnp.ones((1, 33, 64), jnp.float32))
    finally:
        attn.gate_proj_weight._data = keep
    np.testing.assert_allclose(
        np.asarray(half[0]),
        0.5 * np.asarray(attn.o_proj_weight._data).sum(0)[None].repeat(33, 0),
        atol=1e-5)
    assert np.abs(np.asarray(o - half)).max() > 1e-3


def test_scopes_of_a_token_step(f32):
    """pt.attn.window and pt.attn.full inside pt.attn, pt.attn.gate inside
    either, pt.moe's parts as they were."""
    model, _, _ = f32
    caches = model._init_paged_caches(2, 32, page_size=4)
    jaxpr = jax.make_jaxpr(
        lambda t, c, p: model.paged_token_step(t, c, p)[0])(
        jnp.zeros(2, jnp.int32), caches, jnp.zeros(2, jnp.int32))
    stacks = set()

    def walk(jp):
        for eq in jp.eqns:
            stacks.add(str(eq.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eq.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    joined = "\n".join(stacks)
    for want in ("pt.attn/pt.attn.window", "pt.attn/pt.attn.full",
                 "pt.attn.window/pt.attn.gate", "pt.attn.full/pt.attn.gate",
                 "pt.attn.window/pt.kv_write", "pt.moe/pt.moe.router",
                 "pt.moe/pt.moe.shared", "pt.lm_head"):
        assert want in joined, want


# ---- the expert layer ---------------------------------------------------------------

def _expert_layer(first=0, count=8, seed=0):
    from paddle_tpu.incubate.distributed.models.moe import (DroplessMoE,
                                                            SigmoidGate)
    from paddle_tpu.models.afmoe.modeling import AfmoeConfig, AfmoeMLP

    cfg = AfmoeConfig.tiny()
    w = np.random.default_rng(seed)
    layer = DroplessMoE(
        64, 8, 32, gate=SigmoidGate(64, 8, topk=3, scaling=2.826,
                                    norm_eps=1e-20, initializer_range=0.1),
        first=first, count=count, shared=AfmoeMLP(cfg, 32))
    draws = {"router": w.normal(0, 0.1, (64, 8)), "bias": w.normal(0, 0.1, 8),
             "gate": w.normal(0, 0.1, (8, 64, 32)),
             "up": w.normal(0, 0.1, (8, 64, 32)),
             "down": w.normal(0, 0.1, (8, 32, 64)),
             "s_gate": w.normal(0, 0.1, (64, 32)),
             "s_up": w.normal(0, 0.1, (64, 32)),
             "s_down": w.normal(0, 0.1, (32, 64))}
    f = lambda a: jnp.asarray(a, jnp.float32)
    layer.gate.gate_weight._data = f(draws["router"])
    layer.gate.expert_bias._data = f(draws["bias"])
    layer.experts.w_gate._data = f(draws["gate"][first:first + count])
    layer.experts.w_up._data = f(draws["up"][first:first + count])
    layer.experts.w_down._data = f(draws["down"][first:first + count])
    layer.shared.gate_proj_weight._data = f(draws["s_gate"])
    layer.shared.up_proj_weight._data = f(draws["s_up"])
    layer.shared.down_proj_weight._data = f(draws["s_down"])
    return layer, {k: f(v) for k, v in draws.items()}


def test_the_shares_routed_parts_plus_the_shared_expert_once_are_the_layer():
    """Eight shares of one expert each: their outputs, each less the shared
    expert (every chip computes it alike), summed, plus the shared expert
    once, equal the uncut layer, and that equals the reference's uncut
    layer; the reference's own shares add up the same way."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(21, 64)),
                    jnp.float32)
    whole, w = _expert_layer(0, 8)
    shared = whole.shared(x)
    parts = sum(_expert_layer(e, 1)[0](x) - shared for e in range(8))
    np.testing.assert_allclose(np.asarray(parts + shared),
                               np.asarray(whole(x)), atol=2e-5)
    ref = reference()
    rw = {"router": w["router"], "expert_bias": w["bias"],
          "experts_gate": w["gate"], "experts_up": w["up"],
          "experts_down": w["down"]}
    with jax.default_matmul_precision("highest"):
        idx, g, _ = ref.route(rw, x, k=3, renorm=True, scaling=2.826,
                              first=0, held=8)
        both = ref.swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
        want = ref.experts_op(rw, x, idx, g, first=0) + both
        cut = lambda e: {k: (v[e:e + 1] if k.startswith("experts_") else v)
                         for k, v in rw.items()}
        ref_parts = sum(ref.experts_op(cut(e), x, idx, g, first=e)
                        for e in range(8))
    np.testing.assert_allclose(np.asarray(whole(x)), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(ref_parts + both),
                               np.asarray(want), atol=2e-5)


def test_leaf_table_counts_the_cut():
    """The configuration as it is run: 4.27 B parameters (8.5 GB of bf16),
    every published width, all 32 layers."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = json.load(open(os.path.join(
        root, "chipbench", "configs", "trinity-mini-ep8.json")))
    table = reference().leaf_table(cfg)
    count = lambda leaves: sum(int(np.prod(s)) for _, s, _ in leaves)
    total = count(table["top"]) + sum(count(l) for l in table["layers"])
    assert len(table["layers"]) == 32
    assert abs(total / 1e9 - 4.27) < 0.005
    assert cfg["layer_types"].count("sliding_attention") == 24
    assert cfg["reduced"] == ["num_experts", "vocab_size"]
    assert cfg["published"] == {"num_experts": 128, "vocab_size": 200192}
    assert cfg["vocab_size"] * 8 == 200192 and cfg["num_experts"] * 8 == 128
