"""``sample_rows`` against a plain NumPy nucleus sampler, and the structure
its program must keep (tier 1: ``tests/test_serving_sampling.py`` is slow as
a whole).

Since PR 39 the sampler sorts nothing: the kept tokens are found by a search
for their threshold and drawn in id order. The structure tests hold that no
``sort`` is left (``chipbench/metrics/_scopes.py::token_steps`` counted a
decode block's token steps by it and reads nothing now), a ceiling on the
passes over the vocabulary, and that nothing vocabulary-wide is gathered or
drawn; section (f) holds the kept SET against ``nucleus()`` at the cells'
widths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import generation_utils
from paddle_tpu.models.generation_utils import (fold_keys, nucleus_threshold,
                                                sample_rows)

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


def _rig_u(monkeypatch, u):
    monkeypatch.setattr(
        generation_utils.jax.random, "uniform",
        lambda key, shape=(), dtype=jnp.float32, **kw: jnp.full(shape, u, dtype))


LARGEST_U = np.nextafter(np.float32(1), np.float32(0))


@pytest.mark.parametrize("u,which", [(LARGEST_U, "highest"),
                                     (np.float32(0), "lowest")],
                         ids=["largest-u", "u-zero"])
@pytest.mark.parametrize("temperature,top_p,top_k", PARAMS)
def test_the_ends_of_u_choose_the_lowest_and_the_highest_kept_id(
        monkeypatch, u, which, temperature, top_p, top_k):
    """The draw is the inverse CDF in ID order, held to the kept ids."""
    _rig_u(monkeypatch, u)
    for row in (ROW_A, ROW_B):
        kept = np.flatnonzero(nucleus(row, temperature, top_p, top_k))
        tok = int(draw(row, temperature, top_p, top_k, n=1)[0])
        assert tok == (kept[-1] if which == "highest" else kept[0])


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


# ---- the structure: no sort, few passes, nothing V-wide gathered, drawn ----

def _eqns(jaxpr, trips=1):
    """Every equation with how often it runs a call: the product of the
    lengths of the scans round it (a ``fori_loop`` of fixed length is one)."""
    for e in jaxpr.eqns:
        yield e, trips
        inside = trips * e.params["length"] if e.primitive.name == "scan" \
            else trips
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, inside)


def _names(jaxpr):
    return [e.primitive.name for e, _ in _eqns(jaxpr)]


def _search_passes(V):
    """The passes of the two searches: a float32's 32 bits, an id's bits."""
    return 32, (V - 1).bit_length()


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


# a pass = one trip of a search's loop (its reductions share their operands
# and fuse into one read of [rows, V]) or one V-wide reduction outside them
PASS_CEILING = 64


def test_no_sort_and_a_ceiling_on_vocabulary_wide_passes(sampler_eqns):
    V, eqns = sampler_eqns
    names = {e.primitive.name for e, _ in eqns}
    assert not {n for n in names if "sort" in n or "top_k" in n
                or n.startswith("cum") or "reduce_window" in n}
    loops = [e for e, _ in eqns if e.primitive.name == "scan"]
    assert sorted(e.params["length"] for e in loops) == sorted(
        _search_passes(V))
    wide = [(e, trips) for e, trips in eqns
            if e.primitive.name.startswith(("reduce_", "arg"))
            and any(V in v.aval.shape for v in e.invars)]
    assert {trips for _, trips in wide} == {1, *_search_passes(V)}
    # a pass of the threshold's search is a mass and a count, one of the
    # draw's those and the ties' count: nothing else reads the vocabulary there
    loops.sort(key=lambda e: -e.params["length"])
    for loop, reductions in zip(loops, (2, 3)):
        inner = [e for e, _ in _eqns(loop.params["jaxpr"].jaxpr)
                 if any(V in v.aval.shape for v in e.invars)
                 and e.primitive.name.startswith(("reduce_", "arg"))]
        assert len(inner) == reductions
    outside = sum(1 for _, trips in wide if trips == 1)
    assert sum(_search_passes(V)) + outside <= PASS_CEILING


@pytest.mark.parametrize("prim", ["gather", "random_bits"])
def test_nothing_vocabulary_wide_is_gathered_or_drawn(sampler_eqns, prim):
    V, eqns = sampler_eqns
    found = [e for e, _ in eqns if e.primitive.name == prim]
    if prim == "gather":
        assert not found                # no gather at all is left (PR 39)
        return
    assert found                        # the one uniform a row
    for e in found:
        for out in e.outvars:
            assert V not in out.aval.shape, (prim, out.aval.shape)


@pytest.mark.parametrize("do_sample", [False, True],
                         ids=["all-greedy", "sampling"])
def test_a_decode_block_searches_once_a_token_step_or_not_at_all(do_sample):
    """Neither block holds a sort. A ``do_sample=False`` block holds no
    search either; a sampling block holds the two searches inside its scan's
    body, so they run once a token step, and none outside it."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    m = LlamaForCausalLM(cfg)
    eng = ContinuousBatchingEngine(
        m, max_batch=4, max_len=64, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))
    step = eng._build_mega_jit()
    seeds, temps, tops, topks = eng._dev_samp
    closed = jax.make_jaxpr(
        lambda *a: step(*a, n_steps=2, do_sample=do_sample))(
        eng._params, eng._last_tok, eng.caches["kv"], eng.caches["tables"],
        eng._dev_pos, eng._dev_act, seeds, temps, tops, topks)
    assert "sort" not in _names(closed.jaxpr)
    scans = [(e, trips) for e, trips in _eqns(closed.jaxpr)
             if e.primitive.name == "scan"]
    (block,) = [e for e, trips in scans if trips == 1]
    assert block.params["length"] == 2
    searches = sorted(e.params["length"] for e, trips in scans if trips == 2)
    want = sorted(_search_passes(cfg.vocab_size)) if do_sample else []
    assert searches == want and len(scans) == 1 + len(want)


# ---- (f) the kept SET is nucleus()'s support, at the cells' widths ----------
# Float32 sums of 92,544 terms are good to some 1e-7 of the whole and a token
# at the edge of the nucleus weighs 1e-6 to 1e-5 of it, so each case moves its
# top_p into the middle of the gap between the mass before the last kept token
# and before the first dropped one (for the rounded logits: in the middle of
# the run of ties that straddles the edge) and then asks for EXACT equality.

GRID = [(p, k) for p in (0.7, 0.95, 1.0) for k in (0, 1, 50)]
KINDS = {"float32": 0.7, "bf16-ties": 1.0, "minus-inf": 0.7,
         "temperature-1e-6": 1e-6}


def _rows(kind, V, seed):
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((len(GRID), V), dtype=np.float32)
    if kind == "bf16-ties":         # some 170 equal values a step of bf16
        rows = np.asarray(jnp.asarray(rows).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    if kind == "minus-inf":
        rows[:, rng.integers(0, V, 5000)] = -np.inf
        rows[:, -1] = -np.inf       # the highest id: never to be drawn
    return rows


def _kept64(row, temperature, top_p, top_k, in_a_run):
    """``nucleus()``'s rule in float64 with ``top_p`` moved off the edge:
    (kept mask, the tokens of any mass, the moved top_p, ties kept of ties)."""
    lg = row.astype(np.float64) / max(temperature, 1e-6)
    order = np.argsort(-lg, kind="stable")
    p = np.exp(lg[order] - lg[order][0])
    p /= p.sum()
    before = np.cumsum(p) - p
    n = int((before <= top_p).sum())
    ties = (1, 1)
    if n < len(row) and top_p < 1.0:
        if in_a_run:
            run = np.flatnonzero(lg[order] == lg[order][n])
            n = int(run[len(run) // 2])
            ties = (n - int(run[0]), len(run))
        top_p = np.float32(0.5 * (before[n - 1] + before[n]))
        assert before[n] - before[n - 1] > 5e-7
    keep = before <= top_p
    if top_k > 0:
        keep &= np.arange(len(row)) < top_k
    mask = np.zeros(len(row), bool)
    mask[order[keep]] = True
    some_mass = np.zeros(len(row), bool)
    some_mass[order] = p > 0
    return mask, some_mass, np.float32(top_p), ties


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("V", [92544, 65536])
def test_the_kept_set_is_the_nucleus_exactly(monkeypatch, V, kind):
    """Rows of the grid top_p 0.7 / 0.95 / 1.0 x top_k 0 / 1 / 50 in one
    call. A token of no mass (``-inf``; far below the best at temperature
    1e-6) is in neither support and is not compared; nothing else differs."""
    temperature = KINDS[kind]
    rows = _rows(kind, V, seed=V % 1000 + len(kind))
    want = [_kept64(rows[i], temperature, p, k, kind == "bf16-ties")
            for i, (p, k) in enumerate(GRID)]
    temps = jnp.full((len(GRID),), temperature, jnp.float32)
    top_ps = jnp.asarray([w[2] for w in want], jnp.float32)
    top_ks = jnp.asarray([k for _, k in GRID], jnp.int32)
    lg = jnp.asarray(rows) / jnp.maximum(temps[:, None], 1e-6)
    t, n_ties, _, _ = map(np.asarray, jax.jit(nucleus_threshold)(lg, top_ps,
                                                                 top_ks))
    lg = np.asarray(lg)
    inside_a_run = 0
    for i, (mask, some_mass, _, (ties_kept, ties)) in enumerate(want):
        tie = lg[i] == t[i]
        got = (lg[i] > t[i]) | (tie & (np.cumsum(tie) <= n_ties[i]))
        assert np.array_equal(got & some_mass, mask & some_mass), GRID[i]
        assert (mask & some_mass).any()
        if GRID[i][1] == 0 and 1 < ties_kept < ties:
            assert (n_ties[i], tie.sum()) == (ties_kept, ties)
            inside_a_run += 1
    if kind == "bf16-ties":
        assert inside_a_run == 2        # top_p 0.7 and 0.95 without a top_k
    # and the draw stays inside it at both ends of u, in id order
    for u, end in ((LARGEST_U, -1), (np.float32(0), 0)):
        _rig_u(monkeypatch, u)
        toks = np.asarray(sample_rows(
            jnp.asarray(rows), jax.random.split(jax.random.key(2), len(GRID)),
            temps, top_ps, top_ks))
        for i, (mask, some_mass, _, _) in enumerate(want):
            assert toks[i] == np.flatnonzero(mask & some_mass)[end], GRID[i]
