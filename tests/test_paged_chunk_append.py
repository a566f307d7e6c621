"""The packed chunk's append by the page (``ops.paged_attention.
append_paged_chunk``): in a pool the paged kernel reads, a page-aligned
chunk row of ``s`` tokens is ``s // page`` whole pages, written as that many
blocks indexed on the page alone. The bytes are the row form's
(``append_paged_kv``); every other pool, an ``s`` that is no multiple of the
page, and a caller that does not promise alignment (the speculative verify
window) take the row form bit for bit. Also the page count rule that rides
with it (``pool_pages``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.paged_attention import (QuantizedKVPool, _appends_by_page,
                                            _sublanes, append_paged_chunk,
                                            append_paged_kv, kv_pool_shape,
                                            page_append_layers, pool_pages,
                                            unfold_kv_pages)

PAGES, PARK, MAXP = 40, 39, 6       # the parking page is the last one asked for


def _pools(hkv, page, d, dtype, seed=0, stored=True):
    rng = np.random.default_rng(seed)
    n = pool_pages(PAGES, dtype)
    shape = (kv_pool_shape(n, hkv, page, d, dtype) if stored
             else (n, hkv, page, d))
    return tuple(jnp.asarray(rng.normal(size=shape), dtype) for _ in "kv")


def _rows(b, s, hkv, d, dtype, seed=1):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
                 for _ in "kv")


def _row_form(kc, vc, kn, vn, tables, starts):
    """What the layers ran before the page form: ``append_paged_kv`` at
    positions clipped into the table."""
    b, s, hkv, d = kn.shape
    page = kc.shape[2]
    pos = jnp.clip(starts[:, None] + jnp.arange(s, dtype=jnp.int32), 0,
                   tables.shape[1] * page - 1).reshape(-1)
    seq = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
    return append_paged_kv(kc, vc, kn.reshape(b * s, hkv, d),
                           vn.reshape(b * s, hkv, d), tables, pos, seq)


def _numpy_append(pool, new, tables, starts, d):
    """The logical pool [pages, kv_heads, page, d] after the append, with
    pages past the table's width dropped; a parked page takes whatever
    wrote it last and is not compared."""
    out = np.array(unfold_kv_pages(np.asarray(pool.astype(jnp.float32)), d))
    new = np.asarray(new.astype(jnp.float32))
    page = out.shape[2]
    for r in range(new.shape[0]):
        for i in range(new.shape[1]):
            p = int(starts[r]) + i
            if p // page < tables.shape[1]:
                out[tables[r, p // page], :, p % page, :] = new[r, i]
    return out


def _tables():
    """Rows 0 and 1 share a sequence at consecutive chunks; row 2 is another
    sequence whose real pages end inside the chunk (the pad extent parked);
    row 3 is a dummy row: all parked."""
    t = np.full((4, MAXP), PARK, np.int32)
    t[0, :] = t[1, :] = [3, 9, 4, 17, 30, 8]
    t[2, :3] = [21, 5, 12]
    return t


# (id, kv heads, head_dim, page, dtype, chunk tokens, starts in pages)
_PAGE_FORM = [
    ("d128-bf16", 4, 128, 16, jnp.bfloat16, 32, (0, 2, 1, 0)),
    ("d128-f32", 2, 128, 8, jnp.float32, 16, (0, 2, 1, 0)),
    ("d64-bf16-lane-dense", 8, 64, 16, jnp.bfloat16, 32, (2, 4, 0, 0)),
    ("d64-f32-lane-dense", 4, 64, 8, jnp.float32, 24, (0, 3, 0, 0)),
    ("d32-bf16-lane-dense", 8, 32, 16, jnp.bfloat16, 16, (4, 5, 2, 0)),
    # the last row's chunk runs past the table's width: those pages drop
    ("d128-bf16-past-the-table", 4, 128, 16, jnp.bfloat16, 48, (0, 3, 4, 5)),
    ("d64-f32-past-the-table", 4, 64, 8, jnp.float32, 32, (1, 5, 3, 4)),
]


@pytest.mark.parametrize("case", _PAGE_FORM, ids=lambda c: c[0])
def test_page_form_writes_the_row_forms_bytes(case):
    name, hkv, d, page, dtype, s, start_pages = case
    kc, vc = _pools(hkv, page, d, dtype)
    assert _appends_by_page(kc, s)
    tables = _tables()
    if "past" in name:
        tables[3, :] = [1, 2, 6, 7, 10, 11]       # a real row to the last page
    starts = np.asarray(start_pages, np.int32) * page
    kn, vn = _rows(4, s, hkv, d, dtype)
    f = jax.jit(append_paged_chunk, static_argnames="page_aligned")
    got = f(kc, vc, kn, vn, jnp.asarray(tables), jnp.asarray(starts),
            page_aligned=True)
    # one scatter a pool, on b * s // page indices and whole page blocks
    text = f.lower(kc, vc, kn, vn, jnp.asarray(tables), jnp.asarray(starts),
                   page_aligned=True).as_text()
    n_blocks = 4 * s // page
    assert text.count('"stablehlo.scatter"(') == 2
    el = "bf16" if dtype == jnp.bfloat16 else "f32"
    assert text.count(
        f"x{el}>, tensor<{n_blocks}x1xi32>, tensor<{n_blocks}x") == 2
    row = jax.jit(_row_form)(kc, vc, kn, vn, jnp.asarray(tables),
                             jnp.asarray(starts))
    keep = np.array([p for p in range(kc.shape[0]) if p != PARK])
    last = tables[:, -1]
    for pool, new, a, r in zip((kc, vc), (kn, vn), got, row):
        assert a.shape == pool.shape and a.dtype == pool.dtype
        want = _numpy_append(pool, new, tables, starts, d)
        a32 = unfold_kv_pages(np.asarray(a.astype(jnp.float32)), d)
        np.testing.assert_array_equal(a32[keep], want[keep])
        # the row form clips what runs past the table into the table's last
        # slot; everywhere else, off the parking page, the two agree
        r32 = unfold_kv_pages(np.asarray(r.astype(jnp.float32)), d)
        same = np.ones(r32.shape[:1] + r32.shape[2:3], bool)
        same[PARK] = False
        if "past" in name:
            same[last, -1] = False
        np.testing.assert_array_equal(
            a32.transpose(0, 2, 1, 3)[same], r32.transpose(0, 2, 1, 3)[same])
        # the spare pages after the parking page stay as they were
        np.testing.assert_array_equal(
            np.asarray(a[PAGES:].astype(jnp.float32)),
            np.asarray(pool[PAGES:].astype(jnp.float32)))


def _int8_pools(hkv, page, d):
    rng = np.random.default_rng(3)
    n = pool_pages(PAGES, jnp.int8)

    def pool():
        return QuantizedKVPool(
            jnp.asarray(rng.integers(-90, 90, (n, hkv, page, d)), jnp.int8),
            jnp.asarray(rng.uniform(0.5, 2.0, (n, hkv)), jnp.float32))

    return pool(), pool()


# (id, pools, kv heads, head_dim, dtype of the new rows, chunk tokens, aligned)
_ROW_FORM = [
    ("int8", lambda: _int8_pools(4, 16, 128), 4, 128, jnp.float32, 32, True),
    ("d96", lambda: _pools(4, 16, 96, jnp.bfloat16), 4, 96, jnp.bfloat16, 32,
     True),
    ("logical-d64", lambda: _pools(8, 16, 64, jnp.bfloat16, stored=False), 8,
     64, jnp.bfloat16, 32, True),
    ("half-a-tile-page", lambda: _pools(4, 8, 128, jnp.bfloat16), 4, 128,
     jnp.bfloat16, 16, True),
    ("s-no-page-multiple", lambda: _pools(4, 16, 128, jnp.bfloat16), 4, 128,
     jnp.bfloat16, 24, True),
    ("no-promise", lambda: _pools(4, 16, 128, jnp.bfloat16), 4, 128,
     jnp.bfloat16, 32, False),
]


@pytest.mark.parametrize("case", _ROW_FORM, ids=lambda c: c[0])
def test_everything_else_takes_the_row_form_bit_for_bit(case):
    name, pools, hkv, d, dtype, s, aligned = case
    kc, vc = pools()
    page = kc.shape[2]
    assert _appends_by_page(kc, s) == (name == "no-promise")
    tables = jnp.asarray(_tables())
    # off a page where the caller made no promise: a verify window's rows
    starts = jnp.asarray([0, 2 * page, page, 0], jnp.int32) + (
        0 if aligned else jnp.asarray([5, 7, 0, 0], jnp.int32))
    kn, vn = _rows(4, s, hkv, d, dtype)
    got = jax.jit(append_paged_chunk, static_argnames="page_aligned")(
        kc, vc, kn, vn, tables, starts, page_aligned=aligned)
    want = jax.jit(_row_form)(kc, vc, kn, vn, tables, starts)
    for a, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        keep = np.array([p for p in range(a.shape[0]) if p != PARK])
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32))[keep],
                                      np.asarray(w.astype(jnp.float32))[keep])


def test_page_append_layers_counts_by_shape():
    bf = _pools(4, 16, 128, jnp.bfloat16)
    dense = _pools(8, 16, 64, jnp.bfloat16)
    logical = _pools(8, 16, 64, jnp.bfloat16, stored=False)
    kv = [bf, dense, logical, _int8_pools(4, 16, 128)]
    assert page_append_layers(kv, 128) == 2
    assert page_append_layers(kv, 16) == 2
    assert page_append_layers(kv, 24) == 0


@pytest.mark.parametrize("dtype, rows", [(jnp.float32, 8), (jnp.bfloat16, 16),
                                         (jnp.int8, 32)])
@pytest.mark.parametrize("asked", [1, 16, 17, 3329, 10497, 10512])
def test_pool_pages_rounds_up_to_the_tiles_rows(dtype, rows, asked):
    n = pool_pages(asked, dtype)
    assert rows == _sublanes(dtype)
    assert n % rows == 0 and asked <= n < asked + rows


# ---- the engine: streams, the counter, the spare pages ------------------------

@pytest.fixture(scope="module")
def wide_llama():
    """Tiny llama with two KV heads of 64: its float32 pools are lane-dense
    at page 8, so the packed chunk appends by the page."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=128,
                           num_attention_heads=2, num_key_value_heads=2)
    return cfg, LlamaForCausalLM(cfg)


def _engine(model, **kw):
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    args = dict(max_batch=4, max_len=64, page_size=8, block_size=4,
                prefix_cache=PrefixCacheConfig(prefill_chunk=16,
                                               extra_blocks=8, pack_rows=4))
    args.update(kw)
    return ContinuousBatchingEngine(model, **args)


def _waves(eng, cfg):
    """Cold, then warm on the first wave's prefixes: prompts of one and of
    several chunks, a padded tail, a full-prompt hit (COW) and a hit that
    ends on a page inside a chunk; greedy and seeded sampling."""
    from paddle_tpu.inference.serving import Request

    rng = np.random.default_rng(7)
    ids = lambda n: rng.integers(3, cfg.vocab_size, (n,)).astype(np.int32)
    base = [ids(40), ids(16), ids(21), ids(9)]
    out = []
    for wave in (base, [base[0], base[1], np.concatenate([base[0][:24],
                                                          ids(13)]), ids(33)]):
        reqs = [Request(p, max_new_tokens=6, seed=100 + i,
                        **({} if i % 2 else dict(temperature=0.8,
                                                 top_p=0.9)))
                for i, p in enumerate(wave)]
        for r in reqs:
            eng.add_request(r)
        eng.run_until_done(max_steps=200)
        assert all(r.done and not r.failed for r in reqs)
        out.append([list(r.output) for r in reqs])
    return out


def test_streams_by_the_page_equal_the_row_forms(wide_llama, monkeypatch):
    from paddle_tpu.ops import paged_attention as pa

    cfg, model = wide_llama
    by_page = _engine(model)
    assert by_page.caches["kv"][0][0].shape[1:] == (1, 8, 128)
    assert (by_page.stats["page_append_layers"],
            by_page.stats["kv_layers"]) == (2, 2)
    got = _waves(by_page, cfg)
    assert by_page.stats["prefix_hit_admissions"] >= 3
    assert by_page.stats["cow_copies"] >= 1
    # the same engine with the page form switched off: the row form's streams
    monkeypatch.setattr(pa, "_appends_by_page", lambda pool, s: False)
    by_row = _engine(model)
    assert by_row.stats["page_append_layers"] == 0
    assert _waves(by_row, cfg) == got


def test_engine_counts_page_append_layers_and_keeps_spare_pages_out(wide_llama):
    from paddle_tpu.observability import engine_collector
    from paddle_tpu.ops.paged_attention import pool_num_pages

    _, model = wide_llama
    eng = _engine(model)
    fams = {f.name: f for f in engine_collector(eng)()}
    assert fams["pt_engine_page_append_layers"].kind == "gauge"
    assert fams["pt_engine_page_append_layers"].samples[0][2] == 2.0
    # int8 pools, and an engine that packs no chunk, append by the row
    assert _engine(model, kv_cache="int8").stats["page_append_layers"] == 0
    assert _engine(model, prefix_cache=None).stats["page_append_layers"] == 0
    # the pool: the pages asked for (slots' tables, extra blocks, the parking
    # page) rounded up to the tile's rows; the spare ones lie after the
    # parking page and the allocator never hands one out
    asked = 4 * 8 + 8 + 1
    n = pool_num_pages(eng.caches["kv"])
    assert eng._park == asked - 1 and eng._alloc.num_blocks == asked - 1
    assert n == pool_pages(asked, jnp.float32) and n % 8 == 0 and n > asked
    blocks = eng._alloc.alloc(eng._alloc.num_blocks)
    assert sorted(blocks) == list(range(eng._park))
    assert eng._alloc.alloc(1) is None
    eng._alloc.decref(blocks)
    assert int(np.asarray(eng.caches["tables"]).max()) == eng._park


def test_pack_refuses_an_offset_off_a_page(wide_llama):
    from paddle_tpu.inference.serving import PageAlignmentError, Request

    _, model = wide_llama
    eng = _engine(model)
    req = Request(np.arange(3, 43, dtype=np.int32), max_new_tokens=2)
    slot = eng._free_slots[0]
    assert eng._try_admit_prefix(slot, req, [])
    assert eng._prefill_next[slot] == 0
    eng._prefill_next[slot] = 3
    with pytest.raises(PageAlignmentError, match="PT-SRV-010.*offset 3"):
        eng._run_pack([(slot, req)])
