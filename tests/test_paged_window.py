"""A window in the three read paths of ``ops/paged_attention.py``: the decode
kernel (interpret mode), ``paged_decode_reference`` and the chunk form, each
against a dense masked softmax; windows that start mid-page, contexts
shorter than the window, stale table entries behind the window, and
``window=None`` bit-identical to the call without the argument."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.paged_attention import (
    paged_decode_attention, paged_decode_reference, paged_prefill_attention,
    paged_verify_attention, window_chunk_pages)

PAGE, HKV, GROUP, D = 16, 2, 4, 128
MAXP = 12                                  # 192 positions a row


def _pools(seed, rows, dtype=jnp.float32):
    """A pool of ``rows * MAXP`` pages under a shuffled table, and the
    dense K and V [rows, MAXP * PAGE, HKV, D] it holds."""
    rng = np.random.default_rng(seed)
    n = rows * MAXP
    k = rng.standard_normal((n + 1, HKV, PAGE, D)).astype(np.float32)
    v = rng.standard_normal((n + 1, HKV, PAGE, D)).astype(np.float32)
    tables = rng.permutation(n).astype(np.int32).reshape(rows, MAXP)
    dense = lambda pool: np.swapaxes(pool[tables], 2, 3).reshape(
        rows, MAXP * PAGE, HKV, D)
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype), tables,
            dense(k), dense(v))


def _dense(q, kd, vd, q_pos, window):
    """softmax(q k^T / sqrt(d)) v over keys ``j <= p`` and, with a window,
    ``p - j < window``; q [rows, s, heads, D] at positions q_pos [rows, s]."""
    rows, s, hq, _ = q.shape
    out = np.zeros(q.shape, np.float64)
    for r in range(rows):
        for i in range(s):
            p = int(q_pos[r, i])
            lo = 0 if window is None else max(0, p - window + 1)
            for h in range(hq):
                kk = kd[r, lo:p + 1, h // GROUP].astype(np.float64)
                vv = vd[r, lo:p + 1, h // GROUP].astype(np.float64)
                sc = kk @ q[r, i, h].astype(np.float64) / np.sqrt(D)
                w = np.exp(sc - sc.max())
                out[r, i, h] = (w / w.sum()) @ vv
    return out


def _stale(tables, lens, window, fill):
    """The table with every entry that lies wholly behind the window
    pointed at ``fill`` (a page another sequence may own by now)."""
    t = tables.copy()
    for r, n in enumerate(lens):
        behind = max(0, int(n) - window) // PAGE
        t[r, :behind] = fill
    return t


LENS = [1, 15, 16, 17, 40, 41, 47, 48, 49, 100, 191, 192]


@pytest.mark.parametrize("window", [None, 40, 48, 33, 300])
@pytest.mark.parametrize("path", ["reference", "kernel"])
def test_decode_with_a_window_matches_dense(window, path):
    lens = np.asarray(LENS, np.int32)
    rows = len(lens)
    k, v, tables, kd, vd = _pools(1, rows)
    rng = np.random.default_rng(2)
    q = rng.standard_normal((rows, HKV * GROUP, D)).astype(np.float32)
    want = _dense(q[:, None], kd, vd, (lens - 1)[:, None], window)[:, 0]
    t = tables if window is None else _stale(tables, lens, window,
                                             rows * MAXP)
    kw = {} if window is None else {"window": window}
    if path == "kernel":
        with jax.default_matmul_precision("highest"):
            got = paged_decode_attention(jnp.asarray(q), k, v,
                                         jnp.asarray(t), jnp.asarray(lens),
                                         interpret=True, **kw)
    else:
        got = paged_decode_reference(jnp.asarray(q), k, v, jnp.asarray(t),
                                     jnp.asarray(lens), **kw)
    # the kernel's float32 dots are one bf16 pass where Mosaic compiles
    # them; in interpret mode at "highest" they are float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [None, 40, 48, 33, 300])
@pytest.mark.parametrize("s", [1, 16, 32])
def test_chunk_with_a_window_matches_dense(window, s):
    starts = np.asarray([0, 16, 32, 48, 96, 160], np.int32)
    starts = np.minimum(starts, MAXP * PAGE - s)
    rows = len(starts)
    k, v, tables, kd, vd = _pools(3, rows)
    rng = np.random.default_rng(4)
    q = rng.standard_normal((rows, s, HKV * GROUP, D)).astype(np.float32)
    q_pos = starts[:, None] + np.arange(s)[None]
    want = _dense(q, kd, vd, q_pos, window)
    t = tables
    if window is not None:
        # entries wholly behind the chunk's first query's window are stale
        t = _stale(tables, starts + 1, window, rows * MAXP)
    kw = {} if window is None else {"window": window}
    with jax.default_matmul_precision("highest"):
        got = paged_prefill_attention(jnp.asarray(q), k, v, jnp.asarray(t),
                                      jnp.asarray(starts), **kw)
        ver = paged_verify_attention(jnp.asarray(q), k, v, jnp.asarray(t),
                                     jnp.asarray(starts), **kw)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(ver), np.asarray(got))


def test_window_none_is_bit_identical_to_no_argument():
    lens = np.asarray(LENS, np.int32)
    k, v, tables, _, _ = _pools(5, len(lens))
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((len(lens), HKV * GROUP, D)),
                    jnp.float32)
    args = (q, k, v, jnp.asarray(tables), jnp.asarray(lens))
    for fn, kw in ((paged_decode_reference, {}),
                   (paged_decode_attention, {"interpret": True})):
        np.testing.assert_array_equal(
            np.asarray(fn(*args, **kw)), np.asarray(fn(*args, window=None,
                                                       **kw)))
    qc = jnp.asarray(rng.standard_normal((len(lens), 16, HKV * GROUP, D)),
                     jnp.float32)
    starts = jnp.asarray(np.minimum(lens, MAXP * PAGE - 16) // PAGE * PAGE)
    cargs = (qc, k, v, jnp.asarray(tables), starts)
    np.testing.assert_array_equal(
        np.asarray(paged_prefill_attention(*cargs)),
        np.asarray(paged_prefill_attention(*cargs, window=None)))
    # and a window wider than every context changes no value's set of keys
    np.testing.assert_allclose(
        np.asarray(paged_decode_reference(*args, window=10 ** 6)),
        np.asarray(paged_decode_reference(*args)), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window,s,page,max_pages,want", [
    (2048, 128, 16, 512, 137), (2048, 1, 16, 512, 130),
    (32, 16, 16, 12, 4), (2048, 128, 16, 64, 64)])
def test_window_chunk_pages(window, s, page, max_pages, want):
    assert window_chunk_pages(window, s, page, max_pages) == want


def test_the_window_kernel_reads_bf16_pools():
    lens = np.asarray([5, 40, 100, 192], np.int32)
    k, v, tables, kd, vd = _pools(7, len(lens), jnp.bfloat16)
    rng = np.random.default_rng(8)
    q = rng.standard_normal((len(lens), HKV * GROUP, D)).astype(np.float32)
    qb = jnp.asarray(q, jnp.bfloat16)
    kd, vd = (np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))
              for a in (kd, vd))
    want = _dense(np.asarray(qb.astype(jnp.float32))[:, None], kd, vd,
                  (lens - 1)[:, None], 48)[:, 0]
    got = paged_decode_attention(qb, k, v, jnp.asarray(tables),
                                 jnp.asarray(lens), interpret=True, window=48)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               rtol=3e-2, atol=3e-2)
