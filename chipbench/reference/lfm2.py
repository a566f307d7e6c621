"""Plain reference of the LFM2-MoE block (``model_type`` ``lfm2_moe``).

The equations of the published modelling code, in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. It imports nothing of the program (the nucleus arithmetic
on a position's logits is ``reference/decoder.py``'s, given the tied head).

``x`` is a layer's input [s, hidden]; ``n = RMSNorm(x; op_norm)``,
``h = x + Op(n)``, ``m = RMSNorm(h; ffn_norm)``, ``y = h + FFN(m)``; after
the last layer ``RMSNorm(.; final_norm)`` and the head, which is the embedding
(tied).

  conv layer       ``[B, C, X] = split3(n w_in)``; ``u = B * X``;
                   ``c_t = sum_j conv_k[:, j] * u_{t-2+j}`` (``u_t = 0`` for
                   ``t < 0``); ``Op = (C * c) w_out``.
  attention layer  q (32 x 64), k, v (8 x 64) without bias; q and k through
                   an RMSNorm over their 64 (gains ``q_gain``, ``k_gain``)
                   before rotary (rotate-half, all 64 dims); causal
                   ``softmax(q k^T / 8) v``; ``wo``.
  dense FFN        ``w2(silu(w1 m) * w3 m)`` (the first ``num_dense_layers``).
  expert FFN       ``s = sigmoid(m router)``; the ``num_experts_per_tok``
                   chosen are the largest of ``s + expert_bias``; ``g`` =
                   ``s`` at the chosen, ``g / (sum g + 1e-6)`` when
                   ``norm_topk_prob``, times ``routed_scaling_factor``;
                   ``FFN = sum_k g_k w2_k(silu(w1_k m) * w3_k m)``. No shared
                   expert, no capacity, no token dropped.

It runs a layer at a time: the caller hands a function that yields a layer's
weights (made from the seed by ``harness/weights.py`` out of ``leaf_table``).
The weights are the bfloat16 values the configuration serves, and this module
makes sure of it (``_served``): ``harness/weights.py`` upcasts them with
``astype(bfloat16).astype(float32)``, which the TPU's compiler takes for
nothing, so on the chip the float32 weights arrive unrounded (PERF.md section
6, PR 28: all but 2**-16 of a leaf's elements differed from the CPU's draw)
and every weight is up to 2**-9 off what the program multiplies by.

Only the chosen (token, expert) pairs are computed: the pairs are sorted by
expert into tiles of ``EXPERT_TILE`` rows, each tile one expert's, and a loop
over the tiles multiplies each by its expert's matrices (all 64 experts for
every token would be 16 times the work, minutes at "highest").

Where the choice of experts is all but a tie. A routed layer is not a
continuous function of its input: where a token's fourth and fifth ``s + b``
lie closer than rounding moves them, a bfloat16 program and this float32
reference each choose rightly and differ by a whole expert's output, a
quarter of the layer's. With seeded weights the gap is under 0.002 in one of
the eight layers at half the positions, and a sound bfloat16 run read up to
1.3 below the reference's best there, where float8 weights read 1.6: the
widest gap over a run's positions could not tell them apart. So
``hidden_states_many`` returns, beside each position's state, how far the
position's choice was from a tie (the least ``4th - 5th`` over its expert
layers), and ``token_stats`` gives the two numbers that are read as maxima
over positions (``best_gap``, ``nucleus_gap``) only where that is
``ROUTE_MARGIN`` or more: at the rest the reference has no single answer to
hold the program to. The number read as a mean (``mass_above``) is given at
every position. PERF.md section 2 has the readings ``ROUTE_MARGIN`` was set
from.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference import decoder

HIGHEST = "highest"
CONV, ATTN = "conv", "full_attention"
#: rows of one tile of the expert loop (each tile belongs to one expert)
EXPERT_TILE = 128
#: the least gap between a position's fourth and fifth ``s + b``, over its
#: expert layers, at which the reference's choice of experts is taken for the
#: only right one (module docstring): twice the widest margin at which a
#: sound bfloat16 run on the chip chose otherwise (0.0053), under the widest
#: at which float8 weights still do (0.013-0.017); 1.2-1.5% of positions
ROUTE_MARGIN = 0.01


def _dims(cfg):
    h = cfg["hidden_size"]
    hd = h // cfg["num_attention_heads"]
    return h, hd


def leaf_table(cfg: dict) -> dict:
    """What ``harness/weights.py`` draws: ``(name, shape, kind)`` of every
    leaf, the layers' tables from ``layer_types`` and ``num_dense_layers``.
    Experts are stacked ``[experts, in, out]``; the expert bias is a 1-D
    "normal" leaf (no gain: at the table's std it moves the choice between
    neighbouring scores); the head is the embedding, so no ``head`` leaf."""
    h, hd = _dims(cfg)
    v, f, fe = cfg["vocab_size"], cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e, taps = cfg["num_experts"], cfg["conv_L_cache"]
    ops = {
        CONV: (("op_norm", (h,), "gain"), ("w_in", (h, 3 * h), "normal"),
               ("conv_k", (h, taps), "normal"), ("w_out", (h, h), "normal")),
        ATTN: (("op_norm", (h,), "gain"), ("wq", (h, q), "normal"),
               ("wk", (h, kv), "normal"), ("wv", (h, kv), "normal"),
               ("wo", (q, h), "normal"), ("q_gain", (hd,), "gain"),
               ("k_gain", (hd,), "gain")),
    }
    dense = (("ffn_norm", (h,), "gain"), ("w1", (h, f), "normal"),
             ("w3", (h, f), "normal"), ("w2", (f, h), "normal"))
    routed = (("ffn_norm", (h,), "gain"), ("router", (h, e), "normal"),
              ("expert_bias", (e,), "normal"),
              ("experts_w1", (e, h, fe), "normal"),
              ("experts_w3", (e, h, fe), "normal"),
              ("experts_w2", (e, fe, h), "normal"))
    layers = tuple(
        ops[kind] + (dense if i < cfg["num_dense_layers"] else routed)
        for i, kind in enumerate(cfg["layer_types"]))
    if len(layers) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return {"std": float(cfg.get("initializer_range", 0.02)),
            "top": (("embed", (v, h), "normal"),
                    ("final_norm", (h,), "gain")),
            "layers": layers}


@jax.jit
def _served(weights):
    """Every leaf rounded to bfloat16's values, in float32: the rounding
    written as ``reduce_precision``, which no compiler folds away. Leaves
    that hold such values already (the CPU's, the fp8 control's) pass
    unchanged."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                           mantissa_bits=7), weights)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [s, heads, d] at positions 0..s-1, half-rotated layout."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def conv_op(w, n):
    """The gated short convolution on n [s, h]."""
    s = n.shape[0]
    b_, c_, x_ = jnp.split(n @ w["w_in"], 3, axis=-1)
    u = b_ * x_
    taps = w["conv_k"].shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    c = sum(w["conv_k"][:, j] * padded[j:j + s] for j in range(taps))
    return (c_ * c) @ w["w_out"]


def attention_op(w, n, *, n_heads, n_kv, theta, eps, q_block=1024):
    """Causal grouped-query attention with QK-norm on n [s, h], in query
    blocks so that the score matrix stays [heads, q_block, s]."""
    s = n.shape[0]
    hd = w["wq"].shape[1] // n_heads
    q = _rope(_rms((n @ w["wq"]).reshape(s, n_heads, hd), w["q_gain"], eps),
              theta)
    k = _rope(_rms((n @ w["wk"]).reshape(s, n_kv, hd), w["k_gain"], eps),
              theta)
    v = (n @ w["wv"]).reshape(s, n_kv, hd)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    outs = []
    for lo in range(0, s, q_block):
        hi = min(s, lo + q_block)
        sc = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(hd)
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
    return jnp.concatenate(outs, 0).reshape(s, n_heads * hd) @ w["wo"]


def route(w, m, *, k, renorm, scaling, norm_eps=1e-6):
    """(chosen experts [s, k], gates [s, k], margin [s]) of m [s, h]; the
    margin is the gap in ``s + b`` between the last expert chosen and the
    first one left out."""
    s = jax.nn.sigmoid(m @ w["router"])
    best, idx = jax.lax.top_k(s + w["expert_bias"], k + 1)
    idx = idx[:, :k]
    g = jnp.take_along_axis(s, idx, axis=-1)
    if renorm:
        g = g / (g.sum(-1, keepdims=True) + norm_eps)
    return idx, g * scaling, best[:, k - 1] - best[:, k]


def experts_op(w, m, idx, g, tile=EXPERT_TILE):
    """sum_k g_k * expert_k(m) for the chosen pairs only: the pairs sorted
    by expert, each expert's rows padded up to whole tiles, one loop over
    the tiles."""
    s, h = m.shape
    e, k = w["experts_w1"].shape[0], idx.shape[1]
    flat = idx.reshape(-1)                                    # [s*k]
    order = jnp.argsort(flat, stable=True)
    se = flat[order]
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    padded = -(-sizes // tile) * tile
    start = jnp.cumsum(padded) - padded                       # tile-aligned
    first = jnp.cumsum(sizes) - sizes
    pos = start[se] + jnp.arange(s * k, dtype=jnp.int32) - first[se]
    total = -(-(s * k) // tile) * tile + e * tile             # worst case
    rows = jnp.zeros((total, h), m.dtype).at[pos].set(m[order // k])
    owner = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(total // tile) * tile, side="right"),
        e - 1)

    def one(args):
        x, ex = args
        a = jax.nn.silu(x @ w["experts_w1"][ex]) * (x @ w["experts_w3"][ex])
        return a @ w["experts_w2"][ex]

    y = jax.lax.map(one, (rows.reshape(-1, tile, h), owner))
    y = y.reshape(total, h)[pos] * g.reshape(-1)[order][:, None]
    return jnp.zeros((s, h), m.dtype).at[order // k].add(y)


def layer_forward(w, x, *, kind, routed, n_heads, n_kv, theta, eps, k,
                  renorm, scaling):
    """One layer on x [s, h]: (y [s, h], the router's margin [s], infinite
    in a layer without a router)."""
    n = _rms(x, w["op_norm"], eps)
    x = x + (conv_op(w, n) if kind == CONV else attention_op(
        w, n, n_heads=n_heads, n_kv=n_kv, theta=theta, eps=eps))
    m = _rms(x, w["ffn_norm"], eps)
    if not routed:
        return (x + (jax.nn.silu(m @ w["w1"]) * (m @ w["w3"])) @ w["w2"],
                jnp.full(x.shape[:1], jnp.inf, x.dtype))
    idx, g, margin = route(w, m, k=k, renorm=renorm, scaling=scaling)
    return x + experts_op(w, m, idx, g), margin


def _arch(cfg, i):
    return dict(kind=cfg["layer_types"][i],
                routed=i >= cfg["num_dense_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"],
                theta=float(cfg["rope_parameters"]["rope_theta"]),
                eps=float(cfg["norm_eps"]), k=cfg["num_experts_per_tok"],
                renorm=bool(cfg["norm_topk_prob"]),
                scaling=float(cfg["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=(
    "kind", "routed", "n_heads", "n_kv", "theta", "eps", "k", "renorm",
    "scaling"))
def _layer_jit(w, x, **arch):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(w, x, **arch)


def hidden_states_many(cfg, many_ids, layer_fn, top):
    """For each ``ids`` [1, s]: [1, s, h + 1], the final hidden states
    (before the last norm) and, in the last column, how far the position's
    choice of experts was from a tie (the least margin over its expert
    layers). Layer by layer: each layer's weights are asked for once, so
    only one layer's live on the device."""
    embed = _served(top["embed"])
    xs = [jnp.take(embed, jnp.asarray(ids)[0], axis=0) for ids in many_ids]
    del embed
    margins = [jnp.full(x.shape[:1], jnp.inf, x.dtype) for x in xs]
    for i in range(cfg["num_hidden_layers"]):
        w = _served(layer_fn(i))
        for j, x in enumerate(xs):
            xs[j], margin = _layer_jit(w, x, **_arch(cfg, i))
            margins[j] = jnp.minimum(margins[j], margin)
    return [jnp.concatenate([x, m[:, None]], -1)[None]
            for x, m in zip(xs, margins)]


def hidden_states(cfg, ids, layer_fn, top):
    return hidden_states_many(cfg, [ids], layer_fn, top)[0]


def _states(cfg, x):
    """(final hidden states [s, h], route margins [s]) of what
    ``hidden_states_many`` returned for one sequence."""
    h = cfg["hidden_size"]
    return x[..., :h], x[..., h]


def logits_of(cfg, x, top):
    """Logits [s, vocab] of one sequence's ``hidden_states_many``: the tied
    head."""
    top = _served(top)
    with jax.default_matmul_precision(HIGHEST):
        return _rms(_states(cfg, x)[0], top["final_norm"],
                    float(cfg["norm_eps"])) @ top["embed"].T


def _as_untied(cfg, top):
    """The tied head as the dense decoder's reference takes one: what is
    said of a token at a position (``token_stats``, ``draw_tokens``) is that
    family's arithmetic on this family's logits."""
    top = _served(top)
    return ({"rms_norm_eps": cfg["norm_eps"]},
            {"final_norm": top["final_norm"], "head": top["embed"].T})


def token_stats(cfg, x, positions, tokens, top, temperature, top_p):
    """For one sequence's ``hidden_states_many`` ``x`` [s, h + 1]: at each
    of ``positions`` (whose logits predict the token one place on), about
    the given token: ``best_gap``, ``nucleus_gap``, ``mass_above`` and what
    that averages to under a sound sampler (``reference/decoder.py`` defines
    them). Float32 [n] each. Where the position's choice of experts was
    within ``ROUTE_MARGIN`` of a tie, ``best_gap`` reads 0 and
    ``nucleus_gap`` minus infinity: no gap (module docstring)."""
    x, margin = _states(cfg, x)
    cfg, top = _as_untied(cfg, top)
    got = dict(decoder.token_stats(cfg, x, positions, tokens, top,
                                   temperature, top_p))
    tie = margin[jnp.asarray(positions)] < ROUTE_MARGIN
    got["best_gap"] = jnp.where(tie, 0.0, got["best_gap"])
    got["nucleus_gap"] = jnp.where(tie, -jnp.inf, got["nucleus_gap"])
    return got


def draw_tokens(cfg, x, positions, top, temperature, top_p, key):
    """The reference as a sampler, for the controls: at each position its
    first token and one drawn from its nucleus. int32 [n] each."""
    x, _ = _states(cfg, x)
    cfg, top = _as_untied(cfg, top)
    return decoder.draw_tokens(cfg, x, positions, top, temperature, top_p,
                               key)
