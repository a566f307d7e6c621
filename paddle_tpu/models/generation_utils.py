"""Shared KV-cache generation machinery for the causal-LM families.

The model provides two hooks:
  - ``_init_caches(batch, max_len) -> caches`` (pytree of arrays)
  - ``_decode_chunk(ids, caches, pos, pad_bias, pos_offset) ->
    (last_logits [b, vocab] f32, caches)`` — run a chunk at absolute
    positions [pos, pos+s) through the cache path

and the mixin supplies ``generate()``: jitted prefill + 16-token jitted
lax.scan decode blocks (one dispatch per token leaves the device idle
between steps; the block size is not re-measured on the direct runtime),
fused sampling, LEFT-padded batching, eos early-stop with static output
shape, and cache-length bucketing via ``max_length``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor

DECODE_BLOCK = 16


def validate_sampling(temperature, top_p, top_k=0):
    """Shared range checks for sampling params (generate() + serving Request).

    Out-of-range values fail loudly here instead of silently degenerating in
    ``sample_rows`` (e.g. top_p < 0 keeps no candidate at all, and the draw
    then has nothing valid to choose from).
    """
    # `not (x >= 0)` (vs `x < 0`) also rejects NaN
    if temperature is not None and not float(temperature) >= 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and int(top_k) < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")


def _least(holds, nbits, rows):
    """The least ``x`` in ``[0, 2**nbits)`` (uint32 ``[rows]``) at which
    ``holds(x)`` is true, for a ``holds`` that stays true as ``x`` rises and
    is true at the top of the range: a bisection, one bit and one call of
    ``holds`` a pass, highest bit first. (One threshold a pass is what the
    chip timed fastest: the passes run out of the chip's vector memory, so
    several thresholds a pass cost their compares and save no reads;
    PERF.md section 6, PR 39.)"""

    def one_pass(i, lo):
        bit = jnp.uint32(1) << (nbits - 1 - i).astype(jnp.uint32)
        return jnp.where(holds(lo + (bit - 1)), lo, lo + bit)

    return jax.lax.fori_loop(0, nbits, one_pass, jnp.zeros(rows, jnp.uint32))


def _float_at(x):
    """The float32 at place ``x`` (uint32) of the floats' own order, held to
    ``[-inf, inf]``: the places below and above hold only NaNs."""
    x = jnp.clip(x, jnp.uint32(0x007FFFFF), jnp.uint32(0xFF800000))
    bits = jnp.where(x >> 31 == 1, x ^ jnp.uint32(0x80000000), ~x)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def nucleus_threshold(lg, top_ps, top_ks):
    """What ``sample_rows`` keeps of each row of ``lg`` (logits over the
    temperature), as ``(t, n_ties, e, e_t)``: every token with ``lg > t``
    and, of those with ``lg == t``, the first ``n_ties`` by id; ``e`` is
    ``exp(lg - max)`` and ``e_t`` its value at ``t`` (0 where ``t`` is so far
    below the max, or ``-inf``, that a tie weighs nothing).

    That is the prefix of the stable descending order in which the mass
    BEFORE a token is within ``top_p`` and fewer than ``top_k`` tokens lie
    before it: the mass above a value and the count above it both fall as
    the value rises, so the kept values are those from one threshold up. The
    threshold is searched for over the float32 order itself (32 passes, one
    compare-select-reduce over ``[rows, V]`` each), exact at any nucleus
    size; nothing is sorted.

    Named without an underscore because it is the seam the tests read
    (``tests/test_sampler.py`` holds the kept set to its reference's support
    through it); ``sample_rows`` is its one caller in the program."""
    rows, V = lg.shape
    # behind a barrier like lg (sample_rows says why): one e for every pass
    e = jax.lax.optimization_barrier(
        jnp.exp(lg - jnp.max(lg, -1, keepdims=True)))
    budget = top_ps * jnp.sum(e, -1)
    kk = jnp.where(top_ks > 0, top_ks, V)

    def above(t):
        m = lg > t[:, None]
        return (jnp.sum(jnp.where(m, e, 0.0), -1),
                jnp.sum(m, -1, dtype=jnp.int32))

    def holds(x):
        mass, count = above(_float_at(x))
        return (mass <= budget) & (count < kk)

    t = _float_at(_least(holds, 32, rows))
    mass, count = above(t)
    tie = lg == t[:, None]
    e_t = jnp.max(jnp.where(tie, e, 0.0), -1)
    # the ties at t in id order: the r-th is kept while mass + r * e_t is
    # within the budget and count + r under top_k; the first is, by what t is
    fit = jnp.floor((budget - mass) / jnp.where(e_t > 0, e_t, 1.0))
    fit = jnp.where(mass + fit * e_t > budget, fit - 1, fit)
    fit = jnp.where(mass + (fit + 1) * e_t <= budget, fit + 1, fit)
    by_mass = jnp.where(e_t > 0, jnp.clip(fit, 0, V) + 1, V).astype(jnp.int32)
    n_ties = jnp.clip(jnp.minimum(by_mass, kk - count), 1,
                      jnp.sum(tie, -1, dtype=jnp.int32))
    return t, n_ties, e, e_t


@functools.partial(jax.named_call, name="pt.sampler")
def sample_rows(logits, keys, temps, top_ps, top_ks):
    """Row-vectorized sampling: per-row temperature/top-p/top-k/key.

    THE sampling implementation — ``generate()`` and the continuous-batching
    serving engine both draw through it, so their distributions are identical
    by construction (reference sampling op: python/paddle/tensor/search.py:1362
    top_p_sampling).

    logits [b, V] f32; keys: typed PRNG key array [b]; temps/top_ps [b] f32;
    top_ks [b] int32 (0 = disabled). temperature<=0 rows take argmax.

    ``nucleus_threshold`` finds the kept tokens (a search for their
    threshold, no sort of the vocabulary), and one uniform a row draws from
    their renormalised probabilities by the inverse CDF in ID order: the
    least id at which the kept mass up to it passes ``u`` x the whole kept
    mass, found by a second search, over ids (a pass a bit of ``V - 1``), and
    held to the highest kept id. Nothing is V wide but elementwise passes
    fused into reductions: no sort, no cumulative sum, no gather, no field
    of random bits; a token of no mass (``-inf``, or underflowed) is never
    drawn.
    """
    rows, V = logits.shape
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    # ONE lg for every pass below. Without the barrier the compiler
    # recomputes the division inside each fusion of a decode block that
    # reads lg (five of them), and the searches need every pass to see the
    # same float32s: a threshold found on one copy has to equal an entry of
    # the next. On the v5e the copies are NOT equal (a probe read the loop's
    # threshold 1,000 float32 places or more from the maximum another fusion
    # computed), and the block dropped the best token at temperature 1e-6,
    # where a place of lg is half a logit x 1e6 (PERF.md section 6, PR 39;
    # tests/test_tpu_aot_kernels.py holds the compiled block to one division
    # and one exp).
    lg = jax.lax.optimization_barrier(
        logits / jnp.maximum(temps[:, None], 1e-6))
    t, n_ties, e, e_t = nucleus_threshold(lg, top_ps, top_ks)
    # one array for the draw's passes: a token above t weighs its e, a tie
    # -1; ties that weigh nothing are not waited for
    w = jnp.where(lg > t[:, None], e, jnp.where(lg == t[:, None], -1.0, 0.0))
    n_ties = jnp.where(e_t > 0, n_ties, 0)
    ids = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)

    def upto(j):
        """Of the ids <= j: the mass above t, the count above t, the ties."""
        le = ids <= j[:, None]
        return (jnp.sum(jnp.where(le & (w > 0), w, 0.0), -1),
                jnp.sum(le & (w > 0), -1, dtype=jnp.int32),
                jnp.sum(le & (w < 0), -1, dtype=jnp.int32))

    mass_above, n_above, _ = upto(jnp.full((rows,), V - 1, jnp.int32))
    u = jax.vmap(lambda k: jax.random.uniform(k, (1,), jnp.float32))(keys)
    target = u[:, 0] * (mass_above + n_ties * e_t)

    def reached(x):
        # the second clause is counts, so exact: true from the highest kept
        # id on, whatever rounding does to the sums of the first
        mass, above, ties = upto(jnp.minimum(x, V - 1).astype(jnp.int32))
        return ((mass + jnp.minimum(ties, n_ties) * e_t > target)
                | ((above >= n_above) & (ties >= n_ties)))

    sampled = _least(reached, max(V - 1, 1).bit_length(), rows)
    return jnp.where(temps <= 0.0, greedy, sampled.astype(jnp.int32))


@functools.partial(jax.named_call, name="pt.sampler")
def fold_keys(seeds, positions):
    """Stateless per-row keys: fold the token position into the request seed.
    (Under the sampler's scope: the trace puts it with ``sample_rows``.)"""
    return jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.key(s), p))(seeds, positions)


class GenerationMixin:
    def _init_caches(self, b, max_len):
        """Default KV caches [b, max_len, kv_heads, head_dim] per layer; a
        family with a different cache layout (paged KV, MQA) overrides this."""
        cfg = self.config
        kvh = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        hd = cfg.head_dim
        dtype = next(iter(p._data.dtype for _, p in self.named_parameters()))
        return [(jnp.zeros((b, max_len, kvh, hd), dtype),
                 jnp.zeros((b, max_len, kvh, hd), dtype))
                for _ in range(cfg.num_hidden_layers)]

    def _validate_generate(self, prompt_len, max_len):
        """Hook for family-specific length limits (e.g. learned position
        tables); the default (RoPE-style) has none."""

    def _decode_fns(self, temperature, top_p):
        """Jitted prefill/block closures, cached per (temperature, top_p)."""
        key = (float(temperature), top_p)
        cache = getattr(self, "_gen_fns", None)
        if cache is not None and key in cache:
            return cache[key]
        from ..core import autograd_engine
        from ..jit.api import _Swap, _collect_state

        _, tensors = _collect_state(self)

        def sample(logits, skey):
            if temperature == 0.0:
                return jnp.argmax(logits, -1).astype(jnp.int32)
            b = logits.shape[0]
            return sample_rows(
                logits, jax.random.split(skey, b),
                jnp.full((b,), temperature, jnp.float32),
                jnp.full((b,), 1.0 if top_p is None else top_p, jnp.float32),
                jnp.zeros((b,), jnp.int32))

        def run_chunk(ps, chunk, cs, pos, pad_bias, pos_offset, skey):
            with autograd_engine.no_grad(), _Swap(tensors, ps):
                logits, cs = self._decode_chunk(chunk, cs, pos, pad_bias,
                                                pos_offset)
            return sample(logits, skey), cs

        def decode_block(ps, tok, cs, pos0, pad_bias, pos_offset, skey,
                         finished, eos, n_steps):
            def body(carry, i):
                tok, cs, k, fin = carry
                k, sk = jax.random.split(k)
                nxt, cs = run_chunk(ps, tok[:, None], cs, pos0 + i,
                                    pad_bias, pos_offset, sk)
                if eos is not None:
                    nxt = jnp.where(fin, eos, nxt)
                    fin = fin | (nxt == eos)
                return (nxt, cs, k, fin), nxt

            (tok, cs, skey, finished), toks = jax.lax.scan(
                body, (tok, cs, skey, finished), jnp.arange(n_steps))
            return jnp.swapaxes(toks, 0, 1), tok, cs, skey, finished

        # no donate_argnums: the caches are copied once per block instead.
        # Donation here is not measured on the direct runtime.
        prefill = jax.jit(run_chunk)
        block = jax.jit(decode_block, static_argnames=("eos", "n_steps"))
        if cache is None:
            cache = self._gen_fns = {}
        cache[key] = (prefill, block)
        return prefill, block

    def _init_paged_caches(self, b, max_len, page_size=64, num_blocks=None,
                           kv_dtype=None, kv_shards=1):
        """Paged-KV pools (serving layout, ops/paged_attention.py): per-layer
        page pools + a shared block table with pages statically assigned per
        sequence. ``num_blocks`` overrides the pool size (>= b * pages_per_
        seq) for engines that manage pages dynamically — prefix caching
        needs headroom for retained cache blocks plus a parking page.
        ``kv_dtype="int8"`` builds pools in the int8 block format
        (``QuantizedKVPool``: int8 pages + per-(page, head) absmax scales,
        quantize-on-append / dequantize-in-gather — serving.KVCacheConfig).
        Pools of heads narrower than the 128 lanes are made lane-dense where
        the shapes divide (``kv_pool_shape``; ``kv_shards``: the ``tp``
        shards the engine cuts the KV heads into). A pool gets the pages
        asked for rounded up to its dtype's tile rows (``pool_pages``); the
        spare ones lie last and no table names them.
        Families with a different cache layout override this."""
        from ..ops.paged_attention import pool_pages

        cfg = self.config
        kvh = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        hd = cfg.head_dim
        dtype = next(iter(p._data.dtype for _, p in self.named_parameters()))
        maxp = -(-max_len // page_size)
        npages = b * maxp if num_blocks is None else int(num_blocks)
        if npages < b * maxp:
            raise ValueError(f"num_blocks {npages} < {b * maxp} — the pool "
                             "cannot back every slot's table")
        tables = jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)
        npages = pool_pages(npages, jnp.int8 if kv_dtype == "int8" else dtype)
        if kv_dtype == "int8":
            from ..ops.paged_attention import QuantizedKVPool

            def pool():
                return QuantizedKVPool(
                    jnp.zeros((npages, kvh, page_size, hd), jnp.int8),
                    jnp.zeros((npages, kvh), jnp.float32))

            kv = [(pool(), pool()) for _ in range(cfg.num_hidden_layers)]
        elif kv_dtype not in (None, "param"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(supported: None/'param', 'int8')")
        else:
            from ..ops.paged_attention import kv_pool_shape

            shape = kv_pool_shape(npages, kvh, page_size, hd, dtype,
                                  shards=kv_shards)
            kv = [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                  for _ in range(cfg.num_hidden_layers)]
        return {"kv": kv, "tables": tables}

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_p: float = None,
                 eos_token_id: int = None, seed: int = 0,
                 attention_mask=None, max_length: int = None,
                 cache_impl: str = "dense", page_size: int = 64):
        """KV-cache autoregressive generation (greedy / temperature / top-p).

        Batches of unequal prompt lengths use LEFT padding +
        ``attention_mask`` [b, prompt_len] (1 = real): pad columns are
        bias-masked out of attention and positions shift per row so each
        prompt starts at position 0. Always returns [b, max_new_tokens]
        (rows that hit eos early are padded out with eos). ``max_length``
        pins the KV-cache bucket so repeated calls with varying lengths hit
        the compiled-program cache.
        """
        from ..jit.api import _collect_state

        validate_sampling(temperature, top_p)
        ids = (input_ids._data if isinstance(input_ids, Tensor)
               else jnp.asarray(input_ids)).astype(jnp.int32)
        b, prompt_len = ids.shape
        max_len = (max_length if max_length is not None
                   else prompt_len + max_new_tokens)
        if max_len < prompt_len + max_new_tokens:
            raise ValueError(
                f"max_length {max_len} < prompt {prompt_len} + "
                f"max_new_tokens {max_new_tokens}")
        self._validate_generate(prompt_len, prompt_len + max_new_tokens)
        _, tensors = _collect_state(self)
        params = [t._data for t in tensors]
        if cache_impl == "paged":
            if attention_mask is not None:
                raise ValueError(
                    "cache_impl='paged' does not support attention_mask "
                    "(left padding) yet — use equal-length prompts")
            caches = self._init_paged_caches(b, max_len, page_size)
        else:
            caches = self._init_caches(b, max_len)

        if attention_mask is not None:
            m = (attention_mask._data if isinstance(attention_mask, Tensor)
                 else jnp.asarray(attention_mask)).astype(jnp.int32)
            if bool((m[:, -1] == 0).any()) or bool(
                    (jnp.diff(m, axis=1) < 0).any()):
                raise ValueError(
                    "generate() expects LEFT-padded prompts: attention_mask "
                    "must be 0...01...1 per row (pads strictly before tokens)")
            pad_cols = jnp.concatenate(
                [m == 0, jnp.zeros((b, max_len - prompt_len), bool)], axis=1)
            pad_bias = jnp.where(pad_cols, -1e9, 0.0)[:, None, None, :]
            pos_offset = (prompt_len - m.sum(-1)).astype(jnp.int32)
        else:
            pad_bias = None
            pos_offset = None

        prefill, block = self._decode_fns(temperature, top_p)
        key = jax.random.key(seed)
        key, sk = jax.random.split(key)
        tok, caches = prefill(params, ids, caches, 0, pad_bias, pos_offset, sk)
        chunks = [tok[:, None]]
        finished = jnp.zeros((b,), bool)
        if eos_token_id is not None:
            finished = finished | (tok == eos_token_id)
        done = 1
        while done < max_new_tokens:
            if eos_token_id is not None and bool(finished.all()):
                break
            n = min(DECODE_BLOCK, max_new_tokens - done)
            toks, tok, caches, key, finished = block(
                params, tok, caches, prompt_len + done - 1, pad_bias,
                pos_offset, key, finished, eos_token_id, n)
            chunks.append(toks)
            done += n
        out = jnp.concatenate(chunks, axis=1)
        if out.shape[1] < max_new_tokens:
            pad = jnp.full((b, max_new_tokens - out.shape[1]), eos_token_id,
                           jnp.int32)
            out = jnp.concatenate([out, pad], axis=1)
        return Tensor(out)


def causal_cache_bias(k_cache, pos, s, pad_bias=None):
    """[1, 1, s, max_len] additive bias: chunk row i (absolute pos+i) sees
    cache cols <= pos+i; composes with the left-pad bias."""
    max_len = k_cache.shape[1]
    cols = jnp.arange(max_len)[None, :]
    rows = pos + jnp.arange(s)[:, None]
    bias = jnp.where(cols <= rows, 0.0, -1e9)[None, None]
    if pad_bias is not None:
        bias = bias + pad_bias
    return bias
