"""Serving-attention suite: paged/block KV cache, masked decode MHA, fused
transformer blocks (reference: incubate/nn/functional/block_multihead_attention,
masked_multihead_attention, fused_transformer; kernels
phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu etc.).

Pattern per SURVEY §4: every fused op is compared against a plain dense
composition on the same inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.incubate.nn.functional as IF
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops.paged_attention import (
    _kernel_takes,
    append_paged_kv,
    fold_kv_pages,
    gather_paged_kv,
    kv_pool_shape,
    paged_decode_attention,
    paged_decode_reference,
)

# Heavyweight numeric suite: minutes of CPU compute. Excluded from the
# tier-1 fast gate (-m "not slow"); run explicitly or in the nightly pass.
pytestmark = pytest.mark.slow


def _rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _dense_attn(q, k, v, causal=True):
    """[b,s,h,d] reference attention."""
    from paddle_tpu.ops.flash_attention import _xla_reference

    return _xla_reference(q, k, v, causal, q.shape[-1] ** -0.5)


# ---------------------------------------------------------------------------
# paged decode kernel
# ---------------------------------------------------------------------------

def _chunk_tokens(hkv, d, page, maxp, dtype):
    """Tokens of one unit of the kernel's work at these shapes."""
    from paddle_tpu.ops.paged_attention import _decode_chunk_pages

    return page * _decode_chunk_pages(maxp, hkv, page, d,
                                      jnp.dtype(dtype).itemsize)


def _cell_class_lens(hkv, maxp, part_chunk=True):
    """Lengths around every edge of the kernel's units at the serving cells'
    shape classes (128 lanes a row of the pool, page 16, bf16): an empty row,
    one token, exactly a page, exactly a chunk, a chunk plus one, a ragged
    middle and the full table. ``hkv``: the rows of 128 lanes a page holds
    (KV heads of 128, or groups of lane-dense narrower heads)."""
    ct = _chunk_tokens(hkv, 128, 16, maxp, jnp.bfloat16)
    assert ct < maxp * 16
    assert bool((maxp * 16) % ct) == part_chunk, "maxp leaves no part chunk"
    return [0, 1, 16, ct, ct + 1, ct + 16 * 3 + 5, maxp * 16]


# (id, hq, hkv, d, page, maxp, dtype, lens or a function of (hkv, maxp))
_DECODE_CASES = [
    ("group1", 2, 2, 64, 16, 4, jnp.float32, [37, 16, 5]),
    ("group4", 8, 2, 64, 16, 4, jnp.float32, [37, 16, 5]),
    # the chat-batch cell's shape class: GQA 16/8, two query rows a KV head
    ("cell-gqa16x8", 16, 8, 128, 16, 72, jnp.bfloat16, _cell_class_lens),
    # what a tp shard of that model calls the kernel with: its local heads
    ("tp-shard-1kv", 2, 1, 128, 16, 72, jnp.bfloat16, _cell_class_lens),
    ("tp-shard-2kv", 4, 2, 128, 16, 72, jnp.bfloat16, _cell_class_lens),
    # float32 pools, several chunks a row, a table that is no whole number
    # of chunks
    ("f32-chunks", 4, 2, 128, 8, 150, jnp.float32,
     lambda hkv, maxp: [0, 1, 8, 512, 513, 777, 1200]),
    # the chat-batch-64 cell's class: 32/8 heads of 64, two KV heads to a
    # 128-lane row of the pool (f = 2), 160 pages a row: five whole chunks
    ("lfm2-gqa32x8-d64", 32, 8, 64, 16, 160, jnp.bfloat16,
     lambda hkv, maxp: _cell_class_lens(hkv // 2, maxp, part_chunk=False)),
    # f = 4: heads of 32, and one query row a KV head
    ("mha8-d32", 8, 8, 32, 16, 72, jnp.bfloat16,
     lambda hkv, maxp: _cell_class_lens(hkv // 4, maxp)),
    ("f32-d64", 8, 4, 64, 8, 40, jnp.float32,
     lambda hkv, maxp: [0, 1, 8, 300, 320]),
    # what does not divide keeps the logical pool and the dense gather
    ("gqa8x4-d96", 8, 4, 96, 16, 8, jnp.bfloat16, [37, 0, 128]),
    ("one-kv-d64", 4, 1, 64, 16, 8, jnp.bfloat16, [37, 0, 128]),
]
#: the cases whose pools ``kv_pool_shape`` stores lane-dense
LANE_DENSE_CASES = ("lfm2-gqa32x8-d64", "mha8-d32", "f32-d64")
#: narrow heads that keep the logical form (never the kernel's)
LOGICAL_NARROW_CASES = ("gqa8x4-d96", "one-kv-d64")


@pytest.mark.parametrize("case", _DECODE_CASES, ids=lambda c: c[0])
def test_paged_decode_matches_reference(case, monkeypatch):
    name, hq, hkv, d, page, maxp, dtype, lens = case
    if callable(lens):
        lens = lens(hkv, maxp)
    rng = np.random.default_rng(0)
    b = len(lens)
    npages = b * maxp
    q = _rand((b, hq, d), 0, dtype)
    kc = _rand((npages, hkv, page, d), 1, dtype)
    vc = _rand((npages, hkv, page, d), 2, dtype)
    tables = rng.permutation(npages).reshape(b, maxp).astype(np.int32)
    # entries past a row's context are unassigned, as the engine leaves them
    for i, n in enumerate(lens):
        tables[i, -(-n // page):] = -1
    tables = jnp.asarray(tables)
    lens = jnp.asarray(lens, jnp.int32)
    ref = paged_decode_reference(q, kc, vc, tables, lens)
    # the reference reads the logical pools; the kernel the form in which
    # an engine would store them
    stored = kv_pool_shape(npages, hkv, page, d, dtype)
    f = stored[-1] // d
    assert (f > 1) == (d < 128 and name not in LOGICAL_NARROW_CASES)
    kc, vc = fold_kv_pages(kc, f), fold_kv_pages(vc, f)
    assert kc.shape == stored and kc.size == npages * hkv * page * d
    if name in LOGICAL_NARROW_CASES:
        # on a TPU these must not reach Mosaic (lowering it here would fail)
        assert not _kernel_takes(kc)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        out = paged_decode_attention(q, kc, vc, tables, lens)
    else:
        assert _kernel_takes(kc)
        out = paged_decode_attention(q, kc, vc, tables, lens, interpret=True)
        # the gather reads the lane-dense pool as it reads the logical one
        np.testing.assert_array_equal(
            np.asarray(paged_decode_reference(q, kc, vc, tables, lens),
                       np.float32), np.asarray(ref, np.float32))
    assert out.dtype == q.dtype and out.shape == q.shape
    # bf16 results may land one rounding apart
    atol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)
    for i, n in enumerate(np.asarray(lens)):
        if n == 0:
            assert not np.asarray(out[i], np.float32).any()


def test_paged_decode_zero_length_neighbors_intact():
    rng = np.random.default_rng(0)
    b, hq, hkv, d, page, maxp, npages = 3, 8, 2, 64, 16, 4, 16
    q = _rand((b, hq, d), 0)
    kc = _rand((npages, hkv, page, d), 1)
    vc = _rand((npages, hkv, page, d), 2)
    tables = jnp.asarray(rng.permutation(npages)[: b * maxp].reshape(b, maxp),
                         jnp.int32)
    lens = jnp.asarray([37, 0, 23], jnp.int32)  # empty middle row
    ref = paged_decode_reference(q, kc, vc, tables, lens)
    out = paged_decode_attention(q, kc, vc, tables, lens, interpret=True)
    for i in (0, 2):  # row 1 is documented-undefined
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[i]),
                                   atol=2e-5)


@pytest.mark.parametrize("form", ["stored", "logical"])
@pytest.mark.parametrize("d", [32, 64, 96, 128])
def test_append_and_gather_paged_kv_roundtrip(d, form):
    """Both forms of the append's scatter (``_kernel_takes``) and the
    gather's un-fold, read back through ``gather_paged_kv``: the pool as an
    engine stores it (``kv_pool_shape``: lane-dense at 32 and 64, the row
    scatter there and at 128, the slot-major one at 96) and the logical
    pool a caller builds by hand (narrow heads: XLA's gather and the
    slot-major scatter, as ever)."""
    rng = np.random.default_rng(1)
    b, hkv, page, maxp, npages = 3, 4, 8, 4, 12
    logical = (npages, hkv, page, d)
    shape = (kv_pool_shape(npages, hkv, page, d, jnp.float32)
             if form == "stored" else logical)
    assert (shape != logical) == (form == "stored" and d in (32, 64))
    kc, vc = jnp.zeros(shape), jnp.zeros(shape)
    assert _kernel_takes(kc) == (shape[-1] == 128)
    tables = jnp.asarray(rng.permutation(npages).reshape(-1)[: b * maxp]
                         .reshape(b, maxp), jnp.int32)
    lens = np.array([5, 17, 2])
    # prefill-style append: per-seq token runs
    seq_ids = jnp.asarray(np.repeat(np.arange(b), lens), jnp.int32)
    pos = jnp.asarray(np.concatenate([np.arange(n) for n in lens]), jnp.int32)
    kn = _rand((int(lens.sum()), hkv, d), 3)
    vn = _rand((int(lens.sum()), hkv, d), 4)
    kc, vc = append_paged_kv(kc, vc, kn, vn, tables, pos, seq_ids)
    assert kc.shape == shape
    kg, vg = gather_paged_kv(kc, vc, tables, maxp * page, head_dim=d)
    assert kg.shape == (b, maxp * page, hkv, d)
    off = 0
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(kg[i, :n]),
                                   np.asarray(kn[off:off + n]))
        np.testing.assert_allclose(np.asarray(vg[i, :n]),
                                   np.asarray(vn[off:off + n]))
        off += n
    # a decode-style append (one token a row) lands after the runs
    k1, v1 = _rand((b, hkv, d), 5), _rand((b, hkv, d), 6)
    kc, vc = append_paged_kv(kc, vc, k1, v1, tables, jnp.asarray(lens))
    kg, vg = gather_paged_kv(kc, vc, tables, maxp * page, head_dim=d)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(kg[i, n]), np.asarray(k1[i]))
        np.testing.assert_allclose(np.asarray(vg[i, n]), np.asarray(v1[i]))
        np.testing.assert_allclose(np.asarray(kg[i, n - 1]),
                                   np.asarray(kn[lens[:i + 1].sum() - 1]))


# ---------------------------------------------------------------------------
# block_multihead_attention (the serving entry point)
# ---------------------------------------------------------------------------

def _make_blha_batch(lens_np, kv_nh, nh, hd, page, maxp, mode, seed=0):
    """Build reference-layout inputs for block_multihead_attention."""
    b = len(lens_np)
    npages = b * maxp
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.permutation(npages).reshape(b, maxp), jnp.int32)
    kc = jnp.zeros((npages, kv_nh, page, hd))
    vc = jnp.zeros((npages, kv_nh, page, hd))
    if mode == "prefill":
        this_time = lens_np
        enc = lens_np
        dec = np.zeros(b, np.int64)
    else:
        this_time = np.ones(b, np.int64)
        enc = np.zeros(b, np.int64)
        dec = lens_np
    tok = int(this_time.sum())
    qkv = _rand((tok, (nh + 2 * kv_nh) * hd), seed + 1)
    cu_q = np.concatenate([[0], np.cumsum(this_time)])
    return dict(
        qkv=Tensor(qkv), key_cache=Tensor(kc), value_cache=Tensor(vc),
        seq_lens_encoder=Tensor(jnp.asarray(enc, jnp.int32)[:, None]),
        seq_lens_decoder=Tensor(jnp.asarray(dec, jnp.int32)[:, None]),
        seq_lens_this_time=Tensor(jnp.asarray(this_time, jnp.int32)[:, None]),
        padding_offsets=Tensor(jnp.zeros((tok,), jnp.int32)),
        cum_offsets=Tensor(jnp.zeros((b,), jnp.int32)),
        cu_seqlens_q=Tensor(jnp.asarray(cu_q, jnp.int32)[:, None]),
        cu_seqlens_k=Tensor(jnp.asarray(cu_q, jnp.int32)[:, None]),
        block_tables=Tensor(tables),
        block_size=page,
    )


def test_blha_prefill_matches_dense_and_fills_cache():
    kv_nh, nh, hd, page, maxp = 2, 4, 32, 8, 8
    lens = np.array([12, 7, 20])
    kw = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill")
    out, _, kc2, vc2 = IF.block_multihead_attention(**kw)
    qkv = kw["qkv"].numpy().reshape(-1, nh + 2 * kv_nh, hd)
    starts = np.concatenate([[0], np.cumsum(lens)])
    for i, n in enumerate(lens):
        s0, s1 = starts[i], starts[i + 1]
        q = jnp.asarray(qkv[s0:s1, :nh])[None]
        k = jnp.asarray(qkv[s0:s1, nh:nh + kv_nh])[None]
        v = jnp.asarray(qkv[s0:s1, nh + kv_nh:])[None]
        ref = _dense_attn(q, k, v, causal=True)[0].reshape(n, nh * hd)
        np.testing.assert_allclose(out.numpy()[s0:s1], np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
    # cache got the prompt K/V
    kg, _ = gather_paged_kv(kc2._data, vc2._data, kw["block_tables"]._data,
                            maxp * page)
    np.testing.assert_allclose(np.asarray(kg[0, :12]),
                               qkv[:12, nh:nh + kv_nh], atol=1e-6)


def test_blha_decode_matches_dense():
    kv_nh, nh, hd, page, maxp = 2, 4, 32, 8, 8
    prompt_lens = np.array([12, 7, 20])
    kw = _make_blha_batch(prompt_lens, kv_nh, nh, hd, page, maxp, "prefill")
    IF.block_multihead_attention(**kw)  # fills caches in place

    dec_kw = _make_blha_batch(prompt_lens, kv_nh, nh, hd, page, maxp,
                              "decode", seed=7)
    dec_kw["key_cache"] = kw["key_cache"]      # carry the filled caches
    dec_kw["value_cache"] = kw["value_cache"]
    dec_kw["block_tables"] = kw["block_tables"]
    out, _, _, _ = IF.block_multihead_attention(**dec_kw)

    prompt_qkv = kw["qkv"].numpy().reshape(-1, nh + 2 * kv_nh, hd)
    dec_qkv = dec_kw["qkv"].numpy().reshape(-1, nh + 2 * kv_nh, hd)
    starts = np.concatenate([[0], np.cumsum(prompt_lens)])
    for i, n in enumerate(prompt_lens):
        s0, s1 = starts[i], starts[i + 1]
        k_full = np.concatenate([prompt_qkv[s0:s1, nh:nh + kv_nh],
                                 dec_qkv[i:i + 1, nh:nh + kv_nh]])
        v_full = np.concatenate([prompt_qkv[s0:s1, nh + kv_nh:],
                                 dec_qkv[i:i + 1, nh + kv_nh:]])
        q = jnp.asarray(dec_qkv[i:i + 1, :nh])[None]
        ref = _dense_attn(q, jnp.asarray(k_full)[None],
                          jnp.asarray(v_full)[None], causal=True)[0]
        np.testing.assert_allclose(out.numpy()[i], np.asarray(ref).reshape(-1),
                                   atol=2e-5, rtol=2e-5)


def test_blha_mixed_prefill_decode_batch():
    kv_nh, nh, hd, page, maxp = 1, 2, 32, 8, 8
    # seq 0 decodes (8 cached), seq 1 prefills 5 tokens
    b = 2
    rng = np.random.default_rng(3)
    npages = b * maxp
    tables = jnp.asarray(rng.permutation(npages).reshape(b, maxp), jnp.int32)
    kc = jnp.zeros((npages, kv_nh, page, hd))
    vc = jnp.zeros((npages, kv_nh, page, hd))
    # pre-fill seq 0's cache with 8 random tokens
    k_hist = _rand((8, kv_nh, hd), 11)
    v_hist = _rand((8, kv_nh, hd), 12)
    kc, vc = append_paged_kv(kc, vc, k_hist, v_hist, tables,
                             jnp.arange(8, dtype=jnp.int32),
                             jnp.zeros((8,), jnp.int32))
    this_time = np.array([1, 5])
    tok = 6
    qkv = _rand((tok, (nh + 2 * kv_nh) * hd), 13)
    cu = np.array([0, 1, 6])
    out, _, _, _ = IF.block_multihead_attention(
        Tensor(qkv), Tensor(kc), Tensor(vc),
        Tensor(jnp.asarray([0, 5], jnp.int32)[:, None]),
        Tensor(jnp.asarray([8, 0], jnp.int32)[:, None]),
        Tensor(jnp.asarray(this_time, jnp.int32)[:, None]),
        Tensor(jnp.zeros((tok,), jnp.int32)), Tensor(jnp.zeros((b,), jnp.int32)),
        Tensor(jnp.asarray(cu, jnp.int32)[:, None]),
        Tensor(jnp.asarray(cu, jnp.int32)[:, None]),
        Tensor(tables), block_size=page)
    qkv3 = np.asarray(qkv).reshape(tok, nh + 2 * kv_nh, hd)
    # decode row
    kf = np.concatenate([np.asarray(k_hist), qkv3[0:1, nh:nh + kv_nh]])
    vf = np.concatenate([np.asarray(v_hist), qkv3[0:1, nh + kv_nh:]])
    ref0 = _dense_attn(jnp.asarray(qkv3[0:1, :nh])[None],
                       jnp.asarray(kf)[None], jnp.asarray(vf)[None])[0]
    np.testing.assert_allclose(out.numpy()[0], np.asarray(ref0).reshape(-1),
                               atol=2e-5, rtol=2e-5)
    # prefill row
    ref1 = _dense_attn(jnp.asarray(qkv3[1:, :nh])[None],
                       jnp.asarray(qkv3[1:, nh:nh + kv_nh])[None],
                       jnp.asarray(qkv3[1:, nh + kv_nh:])[None])[0]
    np.testing.assert_allclose(out.numpy()[1:], np.asarray(ref1).reshape(5, -1),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# masked_multihead_attention (dense-cache decode)
# ---------------------------------------------------------------------------

def test_mmha_matches_dense_and_updates_cache():
    b, nh, hd, max_seq = 2, 4, 32, 16
    lens = np.array([5, 9])
    cache = np.zeros((2, b, nh, max_seq, hd), np.float32)
    hist_k = np.asarray(_rand((b, nh, max_seq, hd), 0))
    hist_v = np.asarray(_rand((b, nh, max_seq, hd), 1))
    for i, n in enumerate(lens):
        cache[0, i, :, :n] = hist_k[i, :, :n]
        cache[1, i, :, :n] = hist_v[i, :, :n]
    cache_t = Tensor(jnp.asarray(cache))
    x = _rand((b, 3 * nh * hd), 2)
    out, new_cache = IF.masked_multihead_attention(
        Tensor(x), cache_t, sequence_lengths=Tensor(jnp.asarray(lens, jnp.int32)))
    x3 = np.asarray(x).reshape(b, 3, nh, hd)
    for i, n in enumerate(lens):
        kf = np.concatenate([cache[0, i, :, :n], x3[i, 1][:, None]], axis=1)
        vf = np.concatenate([cache[1, i, :, :n], x3[i, 2][:, None]], axis=1)
        logits = np.einsum("nh,nsh->ns", x3[i, 0], kf) * hd ** -0.5
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("ns,nsh->nh", p, vf).reshape(-1)
        np.testing.assert_allclose(out.numpy()[i], ref, atol=2e-5, rtol=2e-5)
        # in-place cache update at position n
        np.testing.assert_allclose(np.asarray(cache_t._data)[0, i, :, n],
                                   x3[i, 1], atol=1e-6)


# ---------------------------------------------------------------------------
# fused_multi_head_attention / fused_feedforward / fused_multi_transformer
# ---------------------------------------------------------------------------

def test_fused_mha_matches_composition():
    b, s, nh, hd = 2, 6, 2, 16
    dim = nh * hd
    x = _rand((b, s, dim), 0)
    qkvw = _rand((3, nh, hd, dim), 1) * 0.2
    lw = _rand((dim, dim), 2) * 0.2
    out = IF.fused_multi_head_attention(
        Tensor(x), Tensor(qkvw), Tensor(lw), pre_layer_norm=True,
        pre_ln_scale=Tensor(jnp.ones(dim)), pre_ln_bias=Tensor(jnp.zeros(dim)),
        dropout_rate=0.0, attn_dropout_rate=0.0)
    # manual composition
    h = np.asarray(x)
    mean = h.mean(-1, keepdims=True)
    var = h.var(-1, keepdims=True)
    hn = (h - mean) / np.sqrt(var + 1e-5)
    qkv = np.einsum("bsd,tnhd->bstnh", hn, np.asarray(qkvw))
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = np.einsum("bqnh,bknh->bnqk", q, k) * hd ** -0.5
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ctx = np.einsum("bnqk,bknh->bqnh", p, v).reshape(b, s, dim)
    ref = np.asarray(x) + ctx @ np.asarray(lw)
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-4, rtol=2e-4)


def test_fused_mha_cache_generation_step():
    b, s, nh, hd = 1, 4, 2, 8
    dim = nh * hd
    x = _rand((b, s, dim), 0)
    qkvw = _rand((3, nh, hd, dim), 1) * 0.3
    lw = _rand((dim, dim), 2) * 0.3
    cache = Tensor(jnp.zeros((2, b, nh, 0, hd)))
    out1, cache_out = IF.fused_multi_head_attention(
        Tensor(x), Tensor(qkvw), Tensor(lw), dropout_rate=0.0,
        attn_dropout_rate=0.0, cache_kv=cache, add_residual=True,
        pre_layer_norm=True)
    assert cache.shape[3] == s  # cache grew in place
    assert cache_out.shape[3] == s


def test_blha_multi_token_continuation():
    # chunked-prefill continuation: dec > 0 with several tokens this time
    kv_nh, nh, hd, page, maxp = 1, 2, 32, 8, 8
    b = 1
    rng = np.random.default_rng(9)
    npages = b * maxp
    tables = jnp.asarray(rng.permutation(npages).reshape(b, maxp), jnp.int32)
    kc = jnp.zeros((npages, kv_nh, page, hd))
    vc = jnp.zeros((npages, kv_nh, page, hd))
    k_hist = _rand((6, kv_nh, hd), 21)
    v_hist = _rand((6, kv_nh, hd), 22)
    kc, vc = append_paged_kv(kc, vc, k_hist, v_hist, tables,
                             jnp.arange(6, dtype=jnp.int32),
                             jnp.zeros((6,), jnp.int32))
    tok = 3
    qkv = _rand((tok, (nh + 2 * kv_nh) * hd), 23)
    out, _, _, _ = IF.block_multihead_attention(
        Tensor(qkv), Tensor(kc), Tensor(vc),
        Tensor(jnp.asarray([0], jnp.int32)[:, None]),
        Tensor(jnp.asarray([6], jnp.int32)[:, None]),
        Tensor(jnp.asarray([tok], jnp.int32)[:, None]),
        Tensor(jnp.zeros((tok,), jnp.int32)), Tensor(jnp.zeros((b,), jnp.int32)),
        Tensor(jnp.asarray([0, tok], jnp.int32)[:, None]),
        Tensor(jnp.asarray([0, tok], jnp.int32)[:, None]),
        Tensor(tables), block_size=page)
    assert float(np.abs(out.numpy()).sum()) > 0  # not the silent-zeros bug
    qkv3 = np.asarray(qkv).reshape(tok, nh + 2 * kv_nh, hd)
    kf = np.concatenate([np.asarray(k_hist), qkv3[:, nh:nh + kv_nh]])
    vf = np.concatenate([np.asarray(v_hist), qkv3[:, nh + kv_nh:]])
    ref = _dense_attn(jnp.asarray(qkv3[:, :nh])[None], jnp.asarray(kf)[None],
                      jnp.asarray(vf)[None], causal=True)[0]
    np.testing.assert_allclose(out.numpy(), np.asarray(ref).reshape(tok, -1),
                               atol=2e-5, rtol=2e-5)


def test_fused_feedforward_matches_composition():
    b, s, dim, hidden = 2, 5, 16, 32
    x = _rand((b, s, dim), 0)
    w1 = _rand((dim, hidden), 1) * 0.2
    w2 = _rand((hidden, dim), 2) * 0.2
    out = IF.fused_feedforward(
        Tensor(x), Tensor(w1), Tensor(w2), dropout1_rate=0.0,
        dropout2_rate=0.0, pre_layer_norm=True,
        ln1_scale=Tensor(jnp.ones(dim)), ln1_bias=Tensor(jnp.zeros(dim)),
        activation="relu")
    h = np.asarray(x)
    hn = (h - h.mean(-1, keepdims=True)) / np.sqrt(h.var(-1, keepdims=True) + 1e-5)
    ref = h + np.maximum(hn @ np.asarray(w1), 0) @ np.asarray(w2)
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-4, rtol=2e-4)


def test_fused_multi_transformer_cache_decode_matches_full():
    """Prefill + token-by-token decode must equal the no-cache full forward."""
    paddle.seed(0)
    b, s, nh, hd, L = 1, 6, 2, 8, 2
    dim = nh * hd
    rng = np.random.default_rng(5)

    def mk(shape, scale=0.2):
        return Tensor(jnp.asarray(rng.normal(size=shape) * scale, jnp.float32))

    ln_s = [mk(dim, 0) + 1.0 for _ in range(L)]
    ln_b = [mk(dim, 0) for _ in range(L)]
    qkvw = [mk((3 * dim, dim)) for _ in range(L)]
    qkvb = [mk(3 * dim) for _ in range(L)]
    lws = [mk((dim, dim)) for _ in range(L)]
    lbs = [mk(dim) for _ in range(L)]
    fln_s = [mk(dim, 0) + 1.0 for _ in range(L)]
    fln_b = [mk(dim, 0) for _ in range(L)]
    w1 = [mk((dim, 2 * dim)) for _ in range(L)]
    b1 = [mk(2 * dim) for _ in range(L)]
    w2 = [mk((2 * dim, dim)) for _ in range(L)]
    b2 = [mk(dim) for _ in range(L)]
    x = Tensor(jnp.asarray(rng.normal(size=(b, s, dim)), jnp.float32))

    common = dict(pre_layer_norm=True, num_heads=nh, dropout_rate=0.0,
                  training=False)
    full = IF.fused_multi_transformer(
        x, ln_s, ln_b, qkvw, qkvb, lws, lbs, fln_s, fln_b, w1, b1, w2, b2,
        **common)

    max_seq = 16
    caches = [Tensor(jnp.zeros((2, b, nh, max_seq, hd))) for _ in range(L)]
    from paddle_tpu.tensor import slice as t_slice  # noqa: F401

    pre = IF.fused_multi_transformer(
        Tensor(x._data[:, : s - 1]), ln_s, ln_b, qkvw, qkvb, lws, lbs,
        fln_s, fln_b, w1, b1, w2, b2, cache_kvs=caches, **common)
    np.testing.assert_allclose(pre.numpy(), full.numpy()[:, : s - 1],
                               atol=2e-4, rtol=2e-4)
    last = IF.fused_multi_transformer(
        Tensor(x._data[:, s - 1:]), ln_s, ln_b, qkvw, qkvb, lws, lbs,
        fln_s, fln_b, w1, b1, w2, b2, cache_kvs=caches, time_step=s - 1,
        **common)
    np.testing.assert_allclose(last.numpy(), full.numpy()[:, s - 1:],
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# in-op rope + int8 KV-cache quant (reference block_multihead_attention.py:54,94)
# ---------------------------------------------------------------------------

def _rope_ref(x, cos_h, sin_h, neox):
    """Reference rope on [tokens, heads, hd] with half tables [tokens, hd/2]."""
    x = np.asarray(x, np.float64)
    hd = x.shape[-1]
    if neox:
        cos = np.concatenate([cos_h, cos_h], -1)[:, None, :]
        sin = np.concatenate([sin_h, sin_h], -1)[:, None, :]
        rot = np.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    else:
        cos = np.repeat(cos_h, 2, -1)[:, None, :]
        sin = np.repeat(sin_h, 2, -1)[:, None, :]
        rot = np.stack([-x[..., 1::2], x[..., 0::2]], -1).reshape(x.shape)
    return x * cos + rot * sin


@pytest.mark.parametrize("neox", [False, True])
def test_blha_in_op_rope_matches_pre_applied(neox):
    """rope_emb inside block_multihead_attention == applying rope to q/k
    beforehand and calling without rope_emb."""
    kv_nh, nh, hd, page, maxp = 2, 4, 32, 8, 8
    lens = np.array([6, 11])
    max_seq = maxp * page
    rng = np.random.default_rng(21)
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    t = np.arange(max_seq)
    fr = np.outer(t, inv)
    rope = np.stack([np.cos(fr), np.sin(fr)])[:, None].repeat(2, 1)
    rope_emb = Tensor(jnp.asarray(rope[:, :, :, None, :], jnp.float32))

    kw = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill", seed=5)
    out_in, _, kc_in, _ = IF.block_multihead_attention(
        **kw, rope_emb=rope_emb, use_neox_style=neox)

    # pre-apply to q/k of each token at its absolute position
    kw2 = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill", seed=5)
    qkv = kw2["qkv"].numpy().reshape(-1, nh + 2 * kv_nh, hd).copy()
    pos = np.concatenate([np.arange(n) for n in lens])
    cos_h, sin_h = np.cos(fr)[pos], np.sin(fr)[pos]
    qkv[:, :nh] = _rope_ref(qkv[:, :nh], cos_h, sin_h, neox)
    qkv[:, nh:nh + kv_nh] = _rope_ref(qkv[:, nh:nh + kv_nh], cos_h, sin_h, neox)
    kw2["qkv"] = Tensor(jnp.asarray(qkv.reshape(len(pos), -1), jnp.float32))
    out_pre, _, kc_pre, _ = IF.block_multihead_attention(**kw2)

    np.testing.assert_allclose(out_in.numpy(), out_pre.numpy(),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(kc_in._data),
                               np.asarray(kc_pre._data), atol=2e-5)


def test_blha_in_op_rope_decode_positions():
    """Decode rows rotate at their own absolute position (dec[i])."""
    kv_nh, nh, hd, page, maxp = 1, 2, 32, 8, 4
    lens = np.array([5, 9])
    max_seq = maxp * page
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    fr = np.outer(np.arange(max_seq), inv)
    rope = np.stack([np.cos(fr), np.sin(fr)])[:, None].repeat(2, 1)
    rope_emb = Tensor(jnp.asarray(rope[:, :, :, None, :], jnp.float32))

    kw = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill", seed=6)
    IF.block_multihead_attention(**kw, rope_emb=rope_emb)
    dec_kw = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "decode", seed=8)
    dec_kw["key_cache"] = kw["key_cache"]
    dec_kw["value_cache"] = kw["value_cache"]
    dec_kw["block_tables"] = kw["block_tables"]
    out, _, _, _ = IF.block_multihead_attention(**dec_kw, rope_emb=rope_emb)

    # manual reference: rope everything, dense attention over the history
    pq = kw["qkv"].numpy().reshape(-1, nh + 2 * kv_nh, hd)
    dq = dec_kw["qkv"].numpy().reshape(-1, nh + 2 * kv_nh, hd)
    starts = np.concatenate([[0], np.cumsum(lens)])
    for i, n in enumerate(lens):
        s0, s1 = starts[i], starts[i + 1]
        pos = np.arange(n)
        kf = _rope_ref(pq[s0:s1, nh:nh + kv_nh], np.cos(fr)[pos], np.sin(fr)[pos], False)
        kd = _rope_ref(dq[i:i + 1, nh:nh + kv_nh], np.cos(fr)[n:n + 1], np.sin(fr)[n:n + 1], False)
        qd = _rope_ref(dq[i:i + 1, :nh], np.cos(fr)[n:n + 1], np.sin(fr)[n:n + 1], False)
        k_full = np.concatenate([kf, kd]).astype(np.float32)
        v_full = np.concatenate([pq[s0:s1, nh + kv_nh:], dq[i:i + 1, nh + kv_nh:]])
        ref = _dense_attn(jnp.asarray(qd, jnp.float32)[None],
                          jnp.asarray(k_full)[None],
                          jnp.asarray(v_full)[None])[0]
        np.testing.assert_allclose(out.numpy()[i], np.asarray(ref).reshape(-1),
                                   atol=2e-5, rtol=2e-5)


def test_blha_int8_cache_quant_close_to_fp():
    """int8 paged cache (static per-head scales): decode matches the fp-cache
    path within quantization tolerance; cache memory is half."""
    kv_nh, nh, hd, page, maxp = 2, 4, 32, 8, 8
    lens = np.array([12, 7])
    # scales sized to the data range: amax ~3 for standard normal
    kq = np.full(kv_nh, 127.0 / 4.0, np.float32)
    scales = dict(
        cache_k_quant_scales=Tensor(jnp.asarray(kq)),
        cache_v_quant_scales=Tensor(jnp.asarray(kq)),
        cache_k_dequant_scales=Tensor(jnp.asarray(1.0 / kq)),
        cache_v_dequant_scales=Tensor(jnp.asarray(1.0 / kq)))

    kw = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill", seed=9)
    kw["key_cache"] = Tensor(jnp.zeros((len(lens) * maxp, kv_nh, page, hd), jnp.int8))
    kw["value_cache"] = Tensor(jnp.zeros((len(lens) * maxp, kv_nh, page, hd), jnp.int8))
    out_q, _, kc_q, vc_q = IF.block_multihead_attention(**kw, **scales)
    assert kc_q._data.dtype == jnp.int8 and vc_q._data.dtype == jnp.int8

    kw_fp = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill", seed=9)
    out_fp, _, _, _ = IF.block_multihead_attention(**kw_fp)
    # prefill outputs are computed from the raw (pre-quant) chunk → exact
    np.testing.assert_allclose(out_q.numpy(), out_fp.numpy(), atol=2e-5)

    # decode step reads the int8 cache — close to fp within int8 tolerance
    dec_q = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "decode", seed=10)
    dec_q["key_cache"], dec_q["value_cache"] = kw["key_cache"], kw["value_cache"]
    dec_q["block_tables"] = kw["block_tables"]
    out_dq, _, _, _ = IF.block_multihead_attention(**dec_q, **scales)

    dec_fp = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "decode", seed=10)
    dec_fp["key_cache"], dec_fp["value_cache"] = kw_fp["key_cache"], kw_fp["value_cache"]
    dec_fp["block_tables"] = kw_fp["block_tables"]
    out_dfp, _, _, _ = IF.block_multihead_attention(**dec_fp)
    err = np.abs(out_dq.numpy() - out_dfp.numpy()).max()
    assert err < 0.05, err                      # int8 cache tolerance
    np.testing.assert_allclose(out_dq.numpy(), out_dfp.numpy(), atol=0.05)


def test_blha_int8_cache_continuation_and_validation():
    kv_nh, nh, hd, page, maxp = 1, 2, 32, 8, 8
    kq = np.full(kv_nh, 127.0 / 4.0, np.float32)
    scales = dict(
        cache_k_quant_scales=Tensor(jnp.asarray(kq)),
        cache_v_quant_scales=Tensor(jnp.asarray(kq)),
        cache_k_dequant_scales=Tensor(jnp.asarray(1.0 / kq)),
        cache_v_dequant_scales=Tensor(jnp.asarray(1.0 / kq)))
    lens = np.array([6])
    kw = _make_blha_batch(lens, kv_nh, nh, hd, page, maxp, "prefill", seed=12)
    kw["key_cache"] = Tensor(jnp.zeros((maxp, kv_nh, page, hd), jnp.int8))
    kw["value_cache"] = Tensor(jnp.zeros((maxp, kv_nh, page, hd), jnp.int8))
    IF.block_multihead_attention(**kw, **scales)

    # 3-token continuation reads the quantized prefix via gather+dequant
    cont = _make_blha_batch(np.array([6]), kv_nh, nh, hd, page, maxp,
                            "decode", seed=13)
    qkv3 = _rand((3, (nh + 2 * kv_nh) * hd), 14)
    cont["qkv"] = Tensor(qkv3)
    cont["seq_lens_this_time"] = Tensor(jnp.asarray([[3]], jnp.int32))
    cont["cu_seqlens_q"] = Tensor(jnp.asarray([[0], [3]], jnp.int32))
    cont["cu_seqlens_k"] = Tensor(jnp.asarray([[0], [3]], jnp.int32))
    cont["key_cache"], cont["value_cache"] = kw["key_cache"], kw["value_cache"]
    cont["block_tables"] = kw["block_tables"]
    out, _, _, _ = IF.block_multihead_attention(**cont, **scales)
    assert np.isfinite(out.numpy()).all()

    # validation: dynamic quant and missing scales raise
    with pytest.raises(NotImplementedError, match="dynamic"):
        IF.block_multihead_attention(**_make_blha_batch(
            lens, kv_nh, nh, hd, page, maxp, "prefill"), **scales,
            use_dynamic_cachekv_quant=True)
    with pytest.raises(ValueError, match="scales"):
        IF.block_multihead_attention(**_make_blha_batch(
            lens, kv_nh, nh, hd, page, maxp, "prefill"),
            cache_k_quant_scales=scales["cache_k_quant_scales"])


@pytest.mark.parametrize("neox", [False, True])
def test_mmha_rotary_matches_pre_applied(neox):
    """rotary_tensor inside masked_multihead_attention == pre-applied rope."""
    b, nh, hd, max_seq = 2, 2, 32, 16
    lens = np.array([5, 9])
    rng = np.random.default_rng(31)
    x = rng.normal(size=(b, 3 * nh * hd)).astype(np.float32)
    cache = rng.normal(size=(2, b, nh, max_seq, hd)).astype(np.float32)
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    fr = np.outer(lens, inv)               # each row at its own position
    cos_h, sin_h = np.cos(fr), np.sin(fr)
    if neox:
        cos = np.concatenate([cos_h, cos_h], -1)
        sin = np.concatenate([sin_h, sin_h], -1)
    else:
        cos = np.repeat(cos_h, 2, -1)
        sin = np.repeat(sin_h, 2, -1)
    rot = np.stack([cos, sin]).reshape(2, b, 1, 1, hd)

    out_in, _ = IF.masked_multihead_attention(
        Tensor(jnp.asarray(x)), Tensor(jnp.asarray(cache)),
        sequence_lengths=Tensor(jnp.asarray(lens, jnp.int32)[:, None]),
        rotary_tensor=Tensor(jnp.asarray(rot, jnp.float32)),
        rotary_emb_dims=1, use_neox_rotary_style=neox)

    # pre-apply rope to q and k of the incoming token
    x3 = x.reshape(b, 3, nh, hd).copy()
    for bi in range(b):
        x3[bi, 0] = _rope_ref(x3[bi, 0][None].transpose(1, 0, 2),
                              cos_h[bi:bi + 1], sin_h[bi:bi + 1], neox
                              ).transpose(1, 0, 2)[0]
        x3[bi, 1] = _rope_ref(x3[bi, 1][None].transpose(1, 0, 2),
                              cos_h[bi:bi + 1], sin_h[bi:bi + 1], neox
                              ).transpose(1, 0, 2)[0]
    out_pre, _ = IF.masked_multihead_attention(
        Tensor(jnp.asarray(x3.reshape(b, -1), jnp.float32)),
        Tensor(jnp.asarray(cache)),
        sequence_lengths=Tensor(jnp.asarray(lens, jnp.int32)[:, None]))
    np.testing.assert_allclose(out_in.numpy(), out_pre.numpy(),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_kernel_int8_interpret():
    """The Pallas decode kernel path (interpret mode) streams int8 pages:
    per-head dequant scales folded into q/out match the fp reference."""
    b, hq, hkv, d, page, maxp = 2, 4, 2, 128, 32, 4
    rng = np.random.default_rng(17)
    lens = jnp.asarray([37, 90], jnp.int32)
    tables = jnp.asarray(rng.permutation(b * maxp).reshape(b, maxp), jnp.int32)
    kf = rng.normal(size=(b * maxp, hkv, page, d)).astype(np.float32)
    vf = rng.normal(size=(b * maxp, hkv, page, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, hq, d)).astype(np.float32))
    ks = np.float32(127.0 / 4.0)
    k8 = jnp.asarray(np.clip(np.round(kf * ks), -127, 127), jnp.int8)
    v8 = jnp.asarray(np.clip(np.round(vf * ks), -127, 127), jnp.int8)

    from paddle_tpu.ops.paged_attention import (paged_decode_attention,
                                                paged_decode_reference)

    # scale folding: K dequant into q, V dequant into out
    out8 = paged_decode_attention(q * (1.0 / ks), k8, v8, tables, lens,
                                  interpret=True) * (1.0 / ks)
    ref = paged_decode_reference(q, jnp.asarray(kf), jnp.asarray(vf),
                                 tables, lens)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref), atol=0.05)
