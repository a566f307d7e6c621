"""Pools of heads narrower than the 128 lanes, stored lane-dense
(``ops.paged_attention.kv_pool_shape``: ``128 // d`` KV heads to a row):
the one decode kernel reads them, the append writes rows in place, whatever
reads a pool through XLA un-folds the gathered pages, and the PTKV1 artifact
keeps the logical order. Tier 1: the kernel runs in interpret mode at the
chat-batch-64 cell's shape class; the cases and bodies are those of the
slow-marked ``test_serving_attention.py``, the lane-dense subset of them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_serving_attention as tsa
from _lfm2_util import TINY, engine, reference_logits, seeded_model, serve
from paddle_tpu.ops.paged_attention import (_kernel_takes, fold_kv_pages,
                                            gather_chain_pages,
                                            kernel_layers, kv_pool_shape,
                                            logical_page_shape,
                                            pool_geometry,
                                            scatter_chain_pages,
                                            unfold_kv_pages)

_NARROW = tsa.LANE_DENSE_CASES + tsa.LOGICAL_NARROW_CASES


@pytest.mark.parametrize(
    "case", [c for c in tsa._DECODE_CASES if c[0] in _NARROW],
    ids=lambda c: c[0])
def test_paged_decode_of_narrow_heads_matches_reference(case, monkeypatch):
    tsa.test_paged_decode_matches_reference(case, monkeypatch)


@pytest.mark.parametrize("form", ["stored", "logical"])
@pytest.mark.parametrize("d", [32, 64, 96, 128])
def test_append_and_gather_roundtrip(d, form):
    tsa.test_append_and_gather_paged_kv_roundtrip(d, form)


@pytest.mark.parametrize("args, want", [
    # the chat-batch-64 cell: two heads of 64 to a row, the same bytes
    ((10497, 8, 16, 64, jnp.bfloat16), (10497, 4, 16, 128)),
    ((64, 8, 16, 32, jnp.bfloat16), (64, 2, 16, 128)),
    ((64, 8, 16, 128, jnp.bfloat16), (64, 8, 16, 128)),
    # what does not divide keeps the logical form
    ((64, 4, 16, 96, jnp.bfloat16), (64, 4, 16, 96)),
    ((64, 8, 16, 80, jnp.bfloat16), (64, 8, 16, 80)),
    ((64, 1, 16, 64, jnp.bfloat16), (64, 1, 16, 64)),
    ((64, 8, 8, 64, jnp.bfloat16), (64, 8, 8, 64)),     # half a bf16 tile
    ((64, 8, 8, 64, jnp.float32), (64, 4, 8, 128)),
    # a tp shard folds its own heads: 4 of 64 on two shards do, on four not
    ((64, 4, 16, 64, jnp.bfloat16, 2), (64, 2, 16, 128)),
    ((64, 4, 16, 64, jnp.bfloat16, 4), (64, 4, 16, 64)),
])
def test_kv_pool_shape_follows_from_shapes_alone(args, want):
    shape = kv_pool_shape(*args)
    assert shape == want and np.prod(shape) == np.prod(args[:4])
    pool = jnp.zeros((2,) + shape[1:], args[4])
    assert logical_page_shape(pool, args[3]) == tuple(args[1:4])
    assert _kernel_takes(pool) == (want[-1] == 128
                                   and want[2] % (32 // pool.dtype.itemsize)
                                   == 0)


def test_fold_and_unfold_are_inverse_and_put_heads_side_by_side():
    x = np.arange(3 * 8 * 4 * 64, dtype=np.float32).reshape(3, 8, 4, 64)
    y = fold_kv_pages(x, 2)
    assert y.shape == (3, 4, 4, 128)
    # row (p, j, s) holds heads 2j and 2j + 1 of slot s side by side
    np.testing.assert_array_equal(y[1, 2, 3, :64], x[1, 4, 3])
    np.testing.assert_array_equal(y[1, 2, 3, 64:], x[1, 5, 3])
    np.testing.assert_array_equal(unfold_kv_pages(y, 64), x)
    np.testing.assert_array_equal(
        np.asarray(unfold_kv_pages(fold_kv_pages(jnp.asarray(x), 2), 64)), x)
    assert fold_kv_pages(x, 1) is x and unfold_kv_pages(x, 64) is x


@pytest.mark.parametrize("d, dtype", [(64, jnp.bfloat16), (32, jnp.float32)])
def test_chain_pages_keep_the_logical_order(d, dtype):
    """``gather_chain_pages`` of a lane-dense pool gives the bytes a
    parent-form pool of the same content exports, and
    ``scatter_chain_pages`` folds them back: a stored artifact still loads,
    into either form."""
    rng = np.random.default_rng(4)
    pages, hkv, page, layers = 12, 4, 16, 2
    logical = [tuple(jnp.asarray(rng.normal(size=(pages, hkv, page, d)), dtype)
                     for _ in range(2)) for _ in range(layers)]
    f = kv_pool_shape(pages, hkv, page, d, dtype)[-1] // d
    assert f == 128 // d
    dense = [tuple(fold_kv_pages(p, f) for p in pair) for pair in logical]
    blocks = [7, 2, 9]
    want = gather_chain_pages(logical, blocks)
    got = gather_chain_pages(dense, blocks, head_dim=d)
    for (wk, wv), (gk, gv) in zip(want, got):
        assert gk.shape == (3, hkv, page, d) and gk.dtype == wk.dtype
        assert gk.tobytes() == wk.tobytes() and gv.tobytes() == wv.tobytes()
    dst = [5, 0, 11]
    for empty, full in ((dense, dense), (logical, logical)):
        into = scatter_chain_pages(
            [tuple(jnp.zeros_like(p) for p in pair) for pair in empty],
            dst, want)
        for (ik, iv), (fk, fv) in zip(into, full):
            np.testing.assert_array_equal(
                np.asarray(ik[jnp.asarray(dst)], np.float32),
                np.asarray(fk[jnp.asarray(blocks)], np.float32))
            np.testing.assert_array_equal(
                np.asarray(iv[jnp.asarray(dst)], np.float32),
                np.asarray(fv[jnp.asarray(blocks)], np.float32))
            assert not np.asarray(ik[1], np.float32).any()
    # a width that is neither the head's nor the lanes' is refused by name
    with pytest.raises(ValueError, match="holds no heads of 48"):
        gather_chain_pages(dense, blocks, head_dim=48)


# ---- engines -----------------------------------------------------------------

#: the lfm2 test family with heads that fold: 4/4 heads of 32, f = 4
_FOLDING = dict(TINY, hidden_size=128, num_attention_heads=4,
                num_key_value_heads=4)


@pytest.fixture(scope="module")
def folding():
    return seeded_model(5, "float32", cfg=_FOLDING)


def test_engine_serves_lane_dense_pools_like_the_reference(folding):
    """An engine whose attention pools are lane-dense (page 8, float32: four
    heads of 32 to a row): chunked prefill, first tokens and decode blocks
    append rows and read through the un-folding gather here on the CPU;
    greedy tokens are the reference's first to 1e-3."""
    from paddle_tpu.inference.serving import Request

    model, top, layer = folding
    eng = engine(model, page_size=8)
    k = eng.caches["kv"][1][0]
    assert k.shape[1:] == (1, 8, 128)
    assert (eng.stats["paged_kernel_layers"], eng.stats["kv_layers"]) == (1, 1)
    rng = np.random.default_rng(3)
    prefix = rng.integers(3, 512, 16).astype(np.int32)
    reqs = [Request(np.concatenate([prefix, rng.integers(
        3, 512, int(rng.integers(3, 20))).astype(np.int32)]),
        max_new_tokens=int(rng.integers(4, 10))) for _ in range(6)]
    outs = serve(eng, reqs)
    assert eng.stats["prefix_hit_admissions"] > 0
    for r, out in zip(reqs, outs):
        lg = reference_logits(np.concatenate([r.prompt, out]), top, layer,
                              cfg=_FOLDING)
        rows = lg[len(r.prompt) - 1: len(r.prompt) - 1 + len(out)]
        assert (rows.max(-1) - rows[np.arange(len(out)), out]).max() < 1e-3


def test_engine_counts_the_layers_the_kernel_reads(folding):
    """``paged_kernel_layers`` of ``kv_layers``: a fact of the build. Page 4
    is half a float32 tile, so the same model's pools stay logical."""
    eng = engine(folding[0], page_size=4)
    assert eng.caches["kv"][1][0].shape[1:] == (4, 4, 32)
    assert (eng.stats["paged_kernel_layers"], eng.stats["kv_layers"]) == (0, 1)
    assert kernel_layers(eng.caches["kv"]) == (0, 1)
    tiny = engine(seeded_model(5, "float32")[0])  # heads of 16, two of them
    assert (tiny.stats["paged_kernel_layers"], tiny.stats["kv_layers"]) == (0, 1)


@pytest.fixture(scope="module")
def llama32():
    """A one-layer llama with 4/4 heads of 32: its pools fold (f = 4)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1, hidden_size=128,
                           num_attention_heads=4, num_key_value_heads=4)
    return cfg, LlamaForCausalLM(cfg)


def _llama_engine(m, logical=False):
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                   block_size=2, prefix_cache=True)
    if logical:
        # the form a parent-built engine held: the ops read a pool's form
        # off its shape, so these take the gather and the slot-major scatter
        cfg = m.config
        eng.caches = dict(eng.caches, kv=[tuple(
            jnp.zeros((p.shape[0], cfg.num_key_value_heads, 8, cfg.head_dim),
                      p.dtype) for p in pair) for pair in eng.caches["kv"]])
    return eng


def test_ptkv1_artifact_is_the_same_from_either_form(llama32):
    """Export from a lane-dense engine and from one holding the parent's
    logical pools: both artifacts state the logical geometry, each loads
    into the other form, and the continued streams are the uninterrupted
    one's. (Byte equality of the pages is the ops' test above: two programs
    round a float32 K one ulp apart on the CPU.)"""
    from paddle_tpu.inference.disagg import KVChainCodec, TieredRouter
    from paddle_tpu.inference.serving import Request

    cfg, m = llama32
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (11,)).astype(np.int32)
    kw = dict(max_new_tokens=8)
    ref = _llama_engine(m)
    assert ref.caches["kv"][0][0].shape[1:] == (1, 8, 128)
    r_ref = Request(prompt, **kw)
    ref.add_request(r_ref)
    ref.run_until_done(max_steps=200)
    codec = KVChainCodec()
    arts = {}
    for form in ("dense", "logical"):
        src = _llama_engine(m, logical=form == "logical")
        req = Request(prompt, **kw)
        src.add_request(req)
        for _ in range(50):
            if src.migration_ready():
                break
            src.step()
        arts[form] = codec.export_chain(src, req.rid)
    heads = {k: codec.peek(a) for k, a in arts.items()}
    for key in ("kvh", "hd", "page_size", "n_written", "pos", "delivered",
                "dtype"):
        assert heads["dense"][key] == heads["logical"][key], key
    assert (heads["dense"]["kvh"], heads["dense"]["hd"]) == (4, 32)
    for form, into in (("dense", True), ("logical", False)):
        dst = _llama_engine(m, logical=into)
        req = codec.import_chain(dst, arts[form])
        dst.run_until_done(max_steps=200)
        assert list(req.tokens) == list(r_ref.tokens), form
    # two engines of one model make one form: the tiers stay compatible
    a, b = _llama_engine(m), _llama_engine(m)
    assert pool_geometry(a.caches["kv"]) == pool_geometry(b.caches["kv"]) == [
        ("kv", (1, 8, 128), "float32")]
    assert TieredRouter._compatible(None, a, b, Request(prompt, **kw), 2)
