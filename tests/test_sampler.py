"""``sample_rows`` against a plain NumPy nucleus sampler, and the structure
its program must keep (tier 1: ``tests/test_serving_sampling.py`` is slow as
a whole).

The benchmark's readers count a decode block's token steps by the sampler's
one ``sort`` (``chipbench/metrics/_scopes.py::token_steps``); the structure
tests hold that line, and that nothing vocabulary-wide is gathered or drawn.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import generation_utils
from paddle_tpu.models.generation_utils import fold_keys, sample_rows

DRAWS = 8192
# twelve tokens, two exact ties (ids 3, 7 and ids 5, 9), nothing so unlikely
# that 8,192 draws could miss it at temperature 1.0
ROW_A = np.array([2.0, 0.5, 1.5, 1.0, -0.5, 0.0, 1.8, 1.0, -1.0, 0.0, 0.7,
                  -0.2], np.float32)
ROW_B = np.array([0.1, 0.0, -0.1, 0.2, 3.0, 0.05, -0.3, 0.15, 2.5, 0.0, -0.05,
                  0.3], np.float32)
ROWS = {"a": ROW_A, "b": ROW_B}
PARAMS = [(0.7, 0.95, 0), (1.0, 1.0, 0), (1.0, 0.5, 0), (0.8, 0.9, 5),
          (1.0, 1.0, 1)]
TV_BOUND = 0.03      # 12 tokens, 8,192 draws: about 0.015 expected at most


def nucleus(row, temperature, top_p, top_k):
    """The exact kept-and-renormalised distribution, by the sampler's rule:
    stable descending order, keep while the mass BEFORE a token is within
    ``top_p``, at most ``top_k`` tokens. Float64, nothing of the program."""
    lg = row.astype(np.float64) / max(temperature, 1e-6)
    order = np.argsort(-lg, kind="stable")
    p = np.exp(lg[order] - lg[order][0])
    p /= p.sum()
    before = np.cumsum(p) - p
    keep = before <= top_p
    if top_k > 0:
        keep &= np.arange(len(row)) < top_k
    out = np.zeros(len(row))
    out[order[keep]] = p[keep] / p[keep].sum()
    return out


def draw(row, temperature, top_p, top_k, n=DRAWS, seed=0):
    logits = jnp.broadcast_to(jnp.asarray(row), (n, len(row)))
    keys = jax.random.split(jax.random.key(seed), n)
    return np.asarray(sample_rows(
        logits, keys, jnp.full((n,), temperature, jnp.float32),
        jnp.full((n,), top_p, jnp.float32), jnp.full((n,), top_k, jnp.int32)))


# ---- (a) the distribution ---------------------------------------------------

@pytest.mark.parametrize("row", sorted(ROWS))
@pytest.mark.parametrize("temperature,top_p,top_k", PARAMS)
def test_support_is_the_nucleus_and_frequencies_match(row, temperature, top_p,
                                                      top_k):
    want = nucleus(ROWS[row], temperature, top_p, top_k)
    toks = draw(ROWS[row], temperature, top_p, top_k)
    freq = np.bincount(toks, minlength=len(want)) / len(toks)
    assert set(np.flatnonzero(freq)) == set(np.flatnonzero(want))
    assert 0.5 * np.abs(freq - want).sum() < TV_BOUND


def test_the_reference_nucleus_is_what_the_cases_assume():
    """The cases are worth their names: a cut that drops tokens, a cut that
    drops none, top_k below the nucleus, and a single survivor."""
    kept = {p: int((nucleus(ROW_A, *p) > 0).sum()) for p in PARAMS}
    assert kept[(1.0, 1.0, 0)] == len(ROW_A)
    assert 1 < kept[(1.0, 0.5, 0)] < kept[(0.7, 0.95, 0)] < len(ROW_A)
    assert kept[(0.8, 0.9, 5)] == 5 and kept[(1.0, 1.0, 1)] == 1


# ---- (b) temperature 1e-6 is an argmax, ties included -----------------------
# (exact ties share the one-hot softmax's mass equally, so each of the tied
# maxima is an argmax and nothing below them is ever drawn)

@pytest.mark.parametrize("row", [ROW_A, ROW_B,
                                 np.array([1.0, 3.0, 3.0, 0.0, 3.0], np.float32),
                                 np.zeros(7, np.float32)],
                         ids=["a", "b", "three-way-tie", "all-equal"])
def test_temperature_1e6_returns_the_argmax_on_every_draw(row):
    toks = draw(row, 1e-6, 0.95, 0, n=512, seed=3)
    assert (row[toks] == row.max()).all()
    if (row == row.max()).sum() == 1:
        assert (toks == int(np.argmax(row))).all()
    else:
        assert set(toks) == set(np.flatnonzero(row == row.max()))


# ---- (c) per-row parameters in one call -------------------------------------

def test_rows_keep_their_own_parameters_and_cold_rows_are_greedy():
    n = 2048
    kinds = [(0.0, 0.9, 0), (-1.0, 0.9, 0), (1.0, 1.0, 1), (1.0, 0.5, 0),
             (0.8, 0.9, 5), (1.0, 1.0, 0)]
    temps, tops, topks = (np.tile(np.array([k[i] for k in kinds]), n)
                          for i in range(3))
    rows = len(kinds) * n
    toks = np.asarray(sample_rows(
        jnp.broadcast_to(jnp.asarray(ROW_B), (rows, len(ROW_B))),
        jax.random.split(jax.random.key(5), rows),
        jnp.asarray(temps, jnp.float32), jnp.asarray(tops, jnp.float32),
        jnp.asarray(topks, jnp.int32))).reshape(n, len(kinds))
    best = int(np.argmax(ROW_B))
    assert (toks[:, :3] == best).all()     # temps <= 0 twice, then top_k 1
    for col, k in enumerate(kinds[3:], start=3):
        want = nucleus(ROW_B, *k)
        freq = np.bincount(toks[:, col], minlength=len(want)) / n
        assert set(np.flatnonzero(freq)) <= set(np.flatnonzero(want))
        assert 0.5 * np.abs(freq - want).sum() < 2 * TV_BOUND


# ---- (d) the key decides, and the largest u stays inside --------------------

def test_same_key_same_token_other_key_other_stream():
    logits = jnp.broadcast_to(jnp.asarray(ROW_A), (64, len(ROW_A)))
    args = (jnp.full((64,), 1.0, jnp.float32), jnp.ones((64,), jnp.float32),
            jnp.zeros((64,), jnp.int32))
    seeds = jnp.arange(64, dtype=jnp.int32)
    one = np.asarray(sample_rows(logits, fold_keys(seeds, seeds + 3), *args))
    two = np.asarray(sample_rows(logits, fold_keys(seeds, seeds + 3), *args))
    other = np.asarray(sample_rows(logits, fold_keys(seeds, seeds + 4), *args))
    assert (one == two).all() and (one != other).any()
    same_key = fold_keys(jnp.full((64,), 9, jnp.int32),
                         jnp.full((64,), 2, jnp.int32))
    assert len(set(np.asarray(sample_rows(logits, same_key, *args)))) == 1


@pytest.mark.parametrize("u,which", [(np.nextafter(np.float32(1), np.float32(0)),
                                      "last"), (np.float32(0), "first")],
                         ids=["largest-u", "u-zero"])
@pytest.mark.parametrize("temperature,top_p,top_k", PARAMS)
def test_the_ends_of_u_choose_the_ends_of_the_kept_prefix(
        monkeypatch, u, which, temperature, top_p, top_k):
    monkeypatch.setattr(
        generation_utils.jax.random, "uniform",
        lambda key, shape=(), dtype=jnp.float32, **kw: jnp.full(shape, u, dtype))
    for row in (ROW_A, ROW_B):
        want = nucleus(row, temperature, top_p, top_k)
        order = np.argsort(-row, kind="stable")
        kept = [t for t in order if want[t] > 0]
        tok = int(draw(row, temperature, top_p, top_k, n=1)[0])
        assert tok == (kept[-1] if which == "last" else kept[0])


# ---- (e) the serving cell's width -------------------------------------------

def test_a_vocabulary_of_92544_runs():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((2, 92544), dtype=np.float32))
    logits = logits.at[0, 77777].set(40.0)          # one token holds the mass
    toks = np.asarray(sample_rows(
        logits, jax.random.split(jax.random.key(1), 2),
        jnp.asarray([0.7, 0.7], jnp.float32),
        jnp.asarray([0.95, 0.95], jnp.float32), jnp.zeros((2,), jnp.int32)))
    assert toks[0] == 77777 and 0 <= toks[1] < 92544
    want = nucleus(np.asarray(logits[1]), 0.7, 0.95, 0)
    assert want[toks[1]] > 0


# ---- the structure the benchmark's readers depend on ------------------------

def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.fixture(scope="module")
def sampler_eqns():
    rows, V = 24, 92544
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)     # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)       # noqa: E731
    closed = jax.make_jaxpr(
        lambda lg, seeds, pos, t, p, k: sample_rows(
            lg, fold_keys(seeds, pos), t, p, k))(
        f32(rows, V), i32(rows), i32(rows), f32(rows), f32(rows), i32(rows))
    return V, list(_eqns(closed.jaxpr))


def test_exactly_one_sort_with_two_operands(sampler_eqns):
    _, eqns = sampler_eqns
    sorts = [e for e in eqns if e.primitive.name == "sort"]
    assert len(sorts) == 1
    (sort,) = sorts
    assert len(sort.invars) == 2 and len(sort.outvars) == 2
    assert sort.params["num_keys"] == 1 and sort.params["is_stable"]
    assert not [e for e in eqns if "top_k" in e.primitive.name]


@pytest.mark.parametrize("prim", ["gather", "random_bits"])
def test_nothing_vocabulary_wide_is_gathered_or_drawn(sampler_eqns, prim):
    V, eqns = sampler_eqns
    found = [e for e in eqns if e.primitive.name == prim]
    assert found                                    # the small ones are there
    for e in found:
        for out in e.outvars:
            assert V not in out.aval.shape, (prim, out.aval.shape)


@pytest.mark.parametrize("do_sample", [False, True],
                         ids=["all-greedy", "sampling"])
def test_a_decode_block_sorts_once_a_token_step_or_not_at_all(do_sample):
    """A ``do_sample=False`` block holds no sort (the readers then count no
    token steps and read nothing); a sampling block holds one, inside its
    scan's body, so it runs once a token step."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    eng = ContinuousBatchingEngine(
        m, max_batch=4, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))
    step = eng._build_mega_jit()
    seeds, temps, tops, topks = eng._dev_samp
    closed = jax.make_jaxpr(
        lambda *a: step(*a, n_steps=2, do_sample=do_sample))(
        eng._params, eng._last_tok, eng.caches["kv"], eng.caches["tables"],
        eng._dev_pos, eng._dev_act, seeds, temps, tops, topks)
    (scan,) = [e for e in _eqns(closed.jaxpr) if e.primitive.name == "scan"]
    assert scan.params["length"] == 2
    n_sorts = lambda j: sum(e.primitive.name == "sort"      # noqa: E731
                            for e in _eqns(j))
    assert n_sorts(scan.params["jaxpr"].jaxpr) == int(do_sample)
    assert n_sorts(closed.jaxpr) == int(do_sample)          # none outside it
