"""``pt_paged_chunk``, the Pallas form of ``paged_prefill_attention``
(interpret mode), against ``paged_prefill_reference``: full and window
layers, heads of 128 at two group sizes and lane-dense heads of 64, ragged
starts; rows that share a table with K and V appended in the same call;
parked dummy rows; stale table entries behind the window and past the row;
query rows whose first visited block is wholly masked; and a query's output
bit for bit the same under two chunkings of its prompt. Off the TPU the
dispatch takes the reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.paged_attention import (append_paged_chunk,
                                            chunk_kernel_layers,
                                            kv_pool_shape,
                                            paged_prefill_attention,
                                            paged_prefill_reference,
                                            paged_verify_attention)

PAGE = 16


@pytest.fixture
def small_blocks(monkeypatch):
    """Key blocks of two pages, so that a table of a dozen pages is a walk
    of several blocks, and query tiles of 32 rows, so that a head's rows
    are several tiles (the call is made anew: its cache keys on neither).
    The tiles are all of one shape: the CPU's matmul rounds a row by the
    shape it sits in, the MXU does not."""
    monkeypatch.setattr(pa, "_CHUNK_MAX_BLOCK_TOKENS", 2 * PAGE)
    monkeypatch.setattr(pa, "_CHUNK_QUERY_TILE", 32)
    pa._chunk_call.cache_clear()
    yield
    pa._chunk_call.cache_clear()


def _case(seed, rows, s, hq, hkv, d, maxp, dtype=jnp.bfloat16):
    """Pools as an engine stores them (lane-dense for heads of 64) under
    shuffled tables, with one page of NaN that no table names."""
    rng = np.random.default_rng(seed)
    n = rows * maxp + 2
    shape = kv_pool_shape(n, hkv, PAGE, d, dtype)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    k[n - 1] = v[n - 1] = np.nan
    tables = rng.permutation(n - 1)[:rows * maxp].astype(np.int32).reshape(
        rows, maxp)
    q = rng.standard_normal((rows, s, hq, d)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), tables, n - 1)


def _poisoned(tables, starts, s, window, nan_page):
    """The table with every entry the kernel may not read pointed at -1 or
    at the page of NaN: past the page of a row's last query, and wholly
    behind its first query's window."""
    t = tables.copy()
    for r, st in enumerate(starts):
        t[r, -(-(int(st) + s) // PAGE):] = nan_page
        if window is not None:
            behind = max(0, int(st) - window + 1) // PAGE
            t[r, :behind] = [-1 if i % 2 else nan_page
                             for i in range(behind)]
    return t


# (id, q heads, kv heads, head_dim): group 2 and 8 of heads of 128, and heads
# of 64 two to a lane-dense row
_HEADS = [("g2-d128", 4, 2, 128), ("g8-d128", 8, 1, 128),
          ("g2-d64-lane-dense", 8, 4, 64)]


@pytest.mark.parametrize("heads", _HEADS, ids=lambda h: h[0])
@pytest.mark.parametrize("window", [None, 2048, 40])
def test_kernel_matches_reference_over_ragged_starts(heads, window):
    """Starts at 0, one page, mid-table, past the window and the table's
    last chunk; the entries the kernel may not read are poisoned for the
    kernel and sound for the reference."""
    _, hq, hkv, d = heads
    s, maxp = 16, 160                       # 2,560 positions a row
    starts = np.asarray([0, 16, 1008, 2064, 2288, maxp * PAGE - s], np.int32)
    q, k, v, tables, nan_page = _case(7, len(starts), s, hq, hkv, d, maxp)
    want = paged_prefill_reference(q, k, v, jnp.asarray(tables),
                                   jnp.asarray(starts), window=window)
    bad = _poisoned(tables, starts, s, window, nan_page)
    got = paged_prefill_attention(q, k, v, jnp.asarray(bad),
                                  jnp.asarray(starts), window=window,
                                  interpret=True)
    ver = paged_verify_attention(q, k, v, jnp.asarray(tables),
                                 jnp.asarray(starts), window=window)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    # the verify window is the same dispatch: off the TPU, the reference
    np.testing.assert_array_equal(np.asarray(ver, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_wholly_masked_blocks_leave_a_row_alone(window, dtype, small_blocks):
    """Blocks of 32 keys under a chunk of 64 queries: the second block of a
    row at start 0 is wholly masked for queries 0-31, and on a window layer
    a late query's first visited blocks lie wholly behind its window."""
    s, maxp = 64, 12
    starts = np.asarray([0, 64, 128, 48], np.int32)
    q, k, v, tables, nan_page = _case(11, len(starts), s, 4, 2, 128, maxp,
                                      dtype)
    with jax.default_matmul_precision("highest"):
        want = paged_prefill_reference(q, k, v, jnp.asarray(tables),
                                       jnp.asarray(starts), window=window)
        got = paged_prefill_attention(
            q, k, v,
            jnp.asarray(_poisoned(tables, starts, s, window, nan_page)),
            jnp.asarray(starts), window=window, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [None, 40])
def test_rows_share_a_table_and_parked_rows_ride_along(window, small_blocks):
    """A pack as the engine makes one: three chunks of ONE sequence at
    successive starts, a row of another, and two parked dummy rows (every
    entry the parking page, start 0), K and V appended in the same jitted
    call before any row reads."""
    s, maxp, hq, hkv, d = 32, 12, 4, 2, 128
    rng = np.random.default_rng(13)
    _, k, v, tables, _ = _case(13, 2, s, hq, hkv, d, maxp)
    park = int(k.shape[0]) - 2               # a page no sequence maps
    rows = np.stack([tables[0], tables[0], tables[0], tables[1],
                     np.full(maxp, park), np.full(maxp, park)]).astype(
                         np.int32)
    starts = np.asarray([64, 96, 128, 32, 0, 0], np.int32)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q, kn, vn = mk(6, s, hq, d), mk(6, s, hkv, d), mk(6, s, hkv, d)

    def run(fn, **kw):
        def body(q, k, v, kn, vn, rows, starts):
            k, v = append_paged_chunk(k, v, kn, vn, rows, starts, True)
            return fn(q, k, v, rows, starts, window=window, **kw)
        return np.asarray(jax.jit(body)(q, k, v, kn, vn, jnp.asarray(rows),
                                        jnp.asarray(starts)), np.float32)

    want = run(paged_prefill_reference)
    got = run(paged_prefill_attention, interpret=True)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("heads", _HEADS, ids=lambda h: h[0])
@pytest.mark.parametrize("window", [None, 40, 100])
def test_a_query_reads_the_same_bits_under_two_chunkings(heads, window,
                                                         small_blocks):
    """The same cache bytes; a prompt prefilled in chunks from 0 (starts 0,
    32, 64, ...) and after a prefix hit of three pages (starts 48, 80,
    ...): every position both chunkings compute comes out bit for bit the
    same, which is what warm == cold rests on. The blocks are aligned to
    absolute positions, so a query meets the same blocks in the same order;
    what differs is which wholly masked blocks lie before and after."""
    _, hq, hkv, d = heads
    s, maxp = 32, 12
    q, k, v, tables, _ = _case(17, 1, maxp * PAGE, hq, hkv, d, maxp)
    table = jnp.asarray(np.repeat(tables, 6, axis=0))

    def outputs(first):
        starts = first + s * np.arange(4, dtype=np.int32)
        qs = jnp.stack([q[0, st:st + s] for st in starts])
        out = paged_prefill_attention(qs, k, v, table[:4],
                                      jnp.asarray(starts), window=window,
                                      interpret=True)
        return {int(st) + i: np.asarray(out[r, i], np.float32)
                for r, st in enumerate(starts) for i in range(s)}

    cold, warm = outputs(0), outputs(48)
    both = sorted(set(cold) & set(warm))
    assert len(both) == 80                   # positions 48 .. 127
    for p in both:
        np.testing.assert_array_equal(cold[p], warm[p], err_msg=str(p))


def test_off_the_tpu_the_dispatch_takes_the_reference(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the kernel was asked for off the TPU")

    monkeypatch.setattr(pa, "_chunk_call", refuse)
    starts = np.asarray([0, 16], np.int32)
    q, k, v, tables, _ = _case(19, 2, 16, 4, 2, 128, 4)
    args = (q, k, v, jnp.asarray(tables), jnp.asarray(starts))
    np.testing.assert_array_equal(
        np.asarray(paged_prefill_attention(*args), np.float32),
        np.asarray(paged_prefill_reference(*args), np.float32))
    with pytest.raises(AssertionError):
        paged_prefill_attention(*args, interpret=True)
    assert chunk_kernel_layers([(k, v)], 128) == 0


@pytest.mark.parametrize("case", [
    # (id, pool shape, dtype, queries a row, taken)
    ("heads-of-128", (9, 4, 16, 128), jnp.bfloat16, 128, True),
    ("lane-dense-64", (9, 4, 16, 128), jnp.bfloat16, 16, True),
    ("f32-page-8", (9, 2, 8, 128), jnp.float32, 8, True),
    ("verify-window-of-5", (9, 4, 16, 128), jnp.bfloat16, 5, False),
    ("chunk-of-1024", (9, 4, 16, 128), jnp.bfloat16, 1024, False),
    ("logical-heads-of-64", (9, 8, 16, 64), jnp.bfloat16, 128, False),
    ("page-of-4", (9, 4, 4, 128), jnp.bfloat16, 128, False),
], ids=lambda c: c[0])
def test_what_the_chunk_kernel_takes(case, monkeypatch):
    _, shape, dtype, s, taken = case
    pool = jnp.zeros(shape, dtype)
    assert pa._chunk_kernel_takes(pool, s) == taken
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert chunk_kernel_layers([(pool, pool), (pool, pool)], s) == 2 * taken
    quant = pa.QuantizedKVPool(jnp.zeros(shape, jnp.int8),
                               jnp.ones(shape[:2], jnp.float32))
    assert chunk_kernel_layers([(quant, quant)], s) == 0


def test_the_engine_counts_the_layers_the_chunk_kernel_serves(monkeypatch):
    """``chunk_kernel_layers``: a fact of the build beside
    ``paged_kernel_layers``; 0 on the CPU, whatever the pools."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import engine_collector

    paddle.seed(11)
    # two KV heads of 64: float32 pools lane-dense at page 8
    model = LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=2, hidden_size=128, num_attention_heads=2,
        num_key_value_heads=2))

    def engine(**kw):
        args = dict(max_batch=4, max_len=64, page_size=8, block_size=4,
                    prefix_cache=PrefixCacheConfig(prefill_chunk=16,
                                                   extra_blocks=8))
        args.update(kw)
        return ContinuousBatchingEngine(model, **args)

    eng = engine()
    assert (eng.stats["paged_kernel_layers"], eng.stats["kv_layers"]) == (2, 2)
    assert eng.stats["chunk_kernel_layers"] == 0
    fams = {f.name: f for f in engine_collector(eng)()}
    assert fams["pt_engine_chunk_kernel_layers"].kind == "gauge"
    assert fams["pt_engine_chunk_kernel_layers"].samples[0][2] == 0.0
    # what an engine built on a TPU counts: every layer whose pools the
    # kernel takes, at a chunk of whole tiles; none for int8 pools, for a
    # chunk that is no whole tile, or where no chunk is packed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engine().stats["chunk_kernel_layers"] == 2
    assert engine(kv_cache="int8").stats["chunk_kernel_layers"] == 0
    assert engine(prefix_cache=None).stats["chunk_kernel_layers"] == 0
