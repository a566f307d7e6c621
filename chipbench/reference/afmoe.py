"""Plain reference of the AFMoE block (``model_type`` ``afmoe``, Trinity).

The equations of the published modelling code, in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: no kernels, no cache, no pages,
no batching tricks. It imports nothing of the program (the nucleus
arithmetic on a position's logits is ``reference/decoder.py``'s). With ``d``
the hidden size, ``W`` = ``sliding_window`` and
``n(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w``:

  embed      ``x = E[ids] * sqrt(d)`` (``mup_enabled``; nothing else is
             scaled at inference).
  attention  ``a = n(x; attn_norm)``; ``q = a wq`` as [s, heads, head_dim],
             ``k = a wk``, ``v = a wv`` as [s, kv heads, head_dim],
             ``g = a wg`` as wide as q; ``q = n(q; q_gain)``,
             ``k = n(k; k_gain)`` over a head's dims; ON A
             ``sliding_attention`` LAYER rotary on q and k over the whole
             head (``rope_theta``, rotate-half), on a ``full_attention``
             layer NONE; scores ``q k^T / sqrt(head_dim)``, key ``j`` visible
             to query ``p`` iff ``j <= p`` and, on a sliding layer, also
             ``p - j < W`` (``W`` keys, the query's own among them);
             ``y = (softmax(scores) v * sigmoid(g)) wo``;
             ``x = x + n(y; post_attn_norm)``.
  FFN        ``b = n(x; pre_mlp_norm)``. The first ``num_dense_layers``
             layers: ``m = (silu(b w_gate) * (b w_up)) w_down``. The others,
             in float32: ``s = sigmoid(b router)`` over the PUBLISHED number
             of experts; the ``num_experts_per_tok`` chosen are the largest
             of ``s + expert_bias`` (``n_group = topk_group = 1``: no group
             limit); gates ``s[chosen] / (sum s[chosen] + 1e-20)`` (where
             ``route_norm``) times ``route_scale``; expert ``e``:
             ``(silu(b gate_e) * (b up_e)) down_e``; the shared expert the
             same form, ``moe_intermediate_size * num_shared_experts`` wide,
             on every token. ``m`` is the gated sum over the chosen experts
             HELD HERE (``num_experts`` of the configuration as it is run,
             ``[0, held)``) plus the shared expert, whole: one chip's share
             of an expert-parallel layer; what the absent experts would add
             is left out. ``x = x + n(m; post_mlp_norm)``.
  head       ``n(x; final_norm) head``, untied.

It runs a layer at a time, on weights rounded to bfloat16's values
(``_served``, as ``reference/lfm2.py`` explains), attention in query blocks
so that a 7k prompt's scores stay [heads, block, keys], and every sequence
past 512 positions at ONE length (``_padded``; the blocks of padding are
skipped), so that a run compiles each kind of layer once and not at fifteen
lengths.

Where the choice of experts is all but a tie, a bfloat16 program and this
reference each choose rightly and differ by a whole expert's output. As in
``reference/nemotron_h.py``, only a flip that involves an expert held here
changes the result by a step, so ``route`` gives as a position's margin the
least gap in ``s + bias`` between a chosen and an unchosen expert over the
pairs of which at least one is held, ``hidden_states_many`` the least of
that over the expert layers, and ``token_stats`` reads the two maxima
(``best_gap``, ``nucleus_gap``) only where it is ``ROUTE_MARGIN`` or more;
the mean (``mass_above``) is read everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import decoder

HIGHEST = "highest"
SLIDING, FULL = "sliding_attention", "full_attention"
#: the least margin (module docstring) at which the reference's choice of
#: experts is taken for the only right one; ``reference/nemotron_h.py``'s,
#: whose router this is (sigmoid, bias in the choice, an eighth held): the
#: cell's ``limits_from`` has the readings it was kept on
ROUTE_MARGIN = 0.005


def routed_experts(cfg) -> int:
    """The router's width: the published number of experts (``published``
    holds it where ``num_experts`` gives what is held here)."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def leaf_table(cfg: dict) -> dict:
    """What ``harness/weights.py`` draws: ``(name, shape, kind)`` of every
    leaf. The four norms of a layer and the two per-head norms are gains;
    the expert bias is a 1-D "normal" leaf (no gain: at the table's std it
    moves the choice between neighbouring scores)."""
    h, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    held, e = cfg["num_experts"], routed_experts(cfg)
    attn = (("attn_norm", (h,), "gain"), ("wq", (h, q), "normal"),
            ("wk", (h, kv), "normal"), ("wv", (h, kv), "normal"),
            ("wg", (h, q), "normal"), ("wo", (q, h), "normal"),
            ("q_gain", (hd,), "gain"), ("k_gain", (hd,), "gain"),
            ("post_attn_norm", (h,), "gain"),
            ("pre_mlp_norm", (h,), "gain"))
    dense = (("w_gate", (h, f), "normal"), ("w_up", (h, f), "normal"),
             ("w_down", (f, h), "normal"))
    routed = (("router", (h, e), "normal"), ("expert_bias", (e,), "normal"),
              ("experts_gate", (held, h, fe), "normal"),
              ("experts_up", (held, h, fe), "normal"),
              ("experts_down", (held, fe, h), "normal"),
              ("shared_gate", (h, fs), "normal"),
              ("shared_up", (h, fs), "normal"),
              ("shared_down", (fs, h), "normal"))
    last = (("post_mlp_norm", (h,), "gain"),)
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return {"std": float(cfg.get("initializer_range", 0.02)),
            "top": (("embed", (v, h), "normal"),
                    ("final_norm", (h,), "gain"),
                    ("head", (h, v), "normal")),
            "layers": tuple(
                attn + (dense if i < cfg["num_dense_layers"] else routed)
                + last for i in range(cfg["num_hidden_layers"]))}


@jax.jit
def _served(weights):
    """Every leaf rounded to bfloat16's values, in float32, written as
    ``reduce_precision``, which no compiler folds away."""
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


def attention_op(w, a, *, n_heads, n_kv, eps, theta, window, rotary,
                 q_block=512, length=None):
    """The gated attention on a [s, h]: the last ``window`` positions where
    it is a number and every one where it is None, rotary on q and k where
    ``rotary`` says so (a sliding layer has both, a full one neither). A
    sequence longer than ``q_block`` goes in blocks of that many queries,
    one body for all of them (``lax.map``: the program does not grow with
    the sequence, which at 7k positions and sixty shapes a run was ten
    minutes of compiling), so the scores stay [heads, q_block, keys]: every
    key for a full layer, the ``window + q_block`` keys a block can see for
    a sliding one. ``length`` (a traced scalar): the positions from it on
    are padding, and a block that holds nothing else is not computed."""
    s = a.shape[0]
    hd = w["wq"].shape[1] // n_heads
    q = _rms((a @ w["wq"]).reshape(s, n_heads, hd), w["q_gain"], eps)
    k = _rms((a @ w["wk"]).reshape(s, n_kv, hd), w["k_gain"], eps)
    v = (a @ w["wv"]).reshape(s, n_kv, hd)
    if rotary:
        q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)

    def block(q_blk, q_pos, k_blk, v_blk, k_pos):
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k_blk) / math.sqrt(hd)
        gap = q_pos[:, None] - k_pos[None, :]
        mask = gap >= 0
        if window is not None:
            mask &= gap < window
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v_blk)

    pos = jnp.arange(s)
    if s <= q_block:
        o = block(q, pos, k, v, pos)
    else:
        n = -(-s // q_block)
        pad = n * q_block - s           # queries past the end: dropped below
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            n, q_block, n_heads, hd)
        span = s if window is None else min(s, window + q_block)

        def one(args):
            q_blk, lo = args
            q_pos = lo + jnp.arange(q_block)
            if span == s:
                return block(q_blk, q_pos, k, v, pos)
            # the keys a block can see: from its first query's window on
            first = jnp.clip(lo - window, 0, s - span)
            return block(q_blk, q_pos,
                         jax.lax.dynamic_slice_in_dim(k, first, span),
                         jax.lax.dynamic_slice_in_dim(v, first, span),
                         first + jnp.arange(span))

        o = jax.lax.map(_unless_padding(one, length, q_block),
                        (qp, jnp.arange(n) * q_block))
        o = o.reshape(n * q_block, n_heads, hd)[:s]
    o = o.reshape(s, n_heads * hd)
    return (o * jax.nn.sigmoid(a @ w["wg"])) @ w["wo"]


def _unless_padding(body, length, rows):
    """``body((x, ..., lo))`` for a block of ``rows`` positions from ``lo``,
    or zeros of its shape where the block lies wholly in the padding past
    ``length`` (None: no padding, every block is computed)."""
    if length is None:
        return body

    def guarded(args):
        shape = jax.eval_shape(body, args)
        return jax.lax.cond(args[-1] < length, body,
                            lambda _: jnp.zeros(shape.shape, shape.dtype),
                            args)

    return guarded


def route(w, b, *, k, renorm, scaling, first, held):
    """(chosen experts [s, k], gates [s, k], margin [s]) of b [s, h]. The
    margin: the least gap in ``s + bias`` between a chosen and an unchosen
    expert over the pairs of which at least one is in ``[first, first +
    held)``; infinite where there is no such pair."""
    s = jax.nn.sigmoid(b @ w["router"])
    pick = s + w["expert_bias"]
    best, idx = jax.lax.top_k(pick, k + 1)
    idx = idx[:, :k]
    g = jnp.take_along_axis(s, idx, axis=-1)
    if renorm:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    e = pick.shape[-1]
    mine = (jnp.arange(e) >= first) & (jnp.arange(e) < first + held)
    chosen = jnp.zeros(pick.shape, bool).at[
        jnp.arange(pick.shape[0])[:, None], idx].set(True)
    low_mine = jnp.min(jnp.where(chosen & mine, pick, jnp.inf), -1)
    high_mine = jnp.max(jnp.where(~chosen & mine, pick, -jnp.inf), -1)
    margin = jnp.minimum(low_mine - best[:, k], best[:, k - 1] - high_mine)
    return idx, g * scaling, margin


def experts_op(w, b, idx, g, *, first, rows=1024, length=None):
    """The gated sum over the chosen experts among the held
    ``[first, first + held)``: every held expert for every position, the
    unchosen weighted 0 (16 experts are cheap enough for that to stay
    plain), ``rows`` positions at a time through one body; blocks wholly
    past ``length`` (padding) are not computed."""
    held = w["experts_up"].shape[0]
    local = idx - first
    gate_of = jnp.sum(jnp.where(
        local[:, :, None] == jnp.arange(held)[None, None, :],
        g[:, :, None], 0.0), axis=1)                            # [s, held]

    def some(args):
        x, go, _ = args
        act = (jax.nn.silu(jnp.einsum("sd,edf->sef", x, w["experts_gate"]))
               * jnp.einsum("sd,edf->sef", x, w["experts_up"])
               * go[:, :, None])
        return jnp.einsum("sef,efd->sd", act, w["experts_down"])

    s = b.shape[0]
    if s <= rows:
        return some((b, gate_of, 0))
    n = -(-s // rows)
    pad = ((0, n * rows - s), (0, 0))
    out = jax.lax.map(_unless_padding(some, length, rows),
                      (jnp.pad(b, pad).reshape(n, rows, -1),
                       jnp.pad(gate_of, pad).reshape(n, rows, held),
                       jnp.arange(n) * rows))
    return out.reshape(n * rows, -1)[:s]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def layer_forward(w, x, *, window, rotary, routed, n_heads, n_kv, eps,
                  theta, k, renorm, scaling, first, length=None):
    """One layer on x [s, h]: (y [s, h], the router's margin [s], infinite
    in a layer without a router). ``length``: the positions from it on are
    padding (what they read is never looked at)."""
    y = attention_op(w, _rms(x, w["attn_norm"], eps), n_heads=n_heads,
                     n_kv=n_kv, eps=eps, theta=theta, window=window,
                     rotary=rotary, length=length)
    x = x + _rms(y, w["post_attn_norm"], eps)
    b = _rms(x, w["pre_mlp_norm"], eps)
    if not routed:
        m = swiglu(b, w["w_gate"], w["w_up"], w["w_down"])
        margin = jnp.full(x.shape[:1], jnp.inf, x.dtype)
    else:
        idx, g, margin = route(w, b, k=k, renorm=renorm, scaling=scaling,
                               first=first, held=w["experts_up"].shape[0])
        m = experts_op(w, b, idx, g, first=first, length=length) + swiglu(
            b, w["shared_gate"], w["shared_up"], w["shared_down"])
    return x + _rms(m, w["post_mlp_norm"], eps), margin


def _arch(cfg, i):
    sliding = cfg["layer_types"][i] == SLIDING
    return dict(window=int(cfg["sliding_window"]) if sliding else None,
                rotary=sliding,
                routed=i >= cfg["num_dense_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"],
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                k=cfg["num_experts_per_tok"],
                renorm=bool(cfg["route_norm"]),
                scaling=float(cfg["route_scale"]),
                first=0)           # the configurations hold experts [0, held)


@functools.partial(jax.jit, static_argnames=(
    "window", "rotary", "routed", "n_heads", "n_kv", "eps", "theta", "k",
    "renorm", "scaling", "first"))
def _layer_jit(w, x, length, **arch):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(w, x, length=length, **arch)


#: sequences longer than this all run at ONE length, the next power of two
#: over the longest of them (zeros after a sequence: causal, so nothing
#: before them changes, and the blocks that hold only zeros are skipped):
#: the harness pads to multiples of 512, fifteen shapes to 7,680 with three
#: kinds of layer each, and every one is 10-17 s of compiling at "highest"
ONE_LENGTH_FROM = 512


def _padded(lens):
    """The length each sequence is run at."""
    longest = max(lens)
    n = ONE_LENGTH_FROM
    while n < longest:
        n *= 2
    return [s if s <= ONE_LENGTH_FROM else n for s in lens]


def hidden_states_many(cfg, many_ids, layer_fn, top):
    """For each ``ids`` [1, s]: [1, s, h + 1], the final hidden states
    (before the last norm) and, in the last column, the position's route
    margin (the least over its expert layers). Layer by layer: each layer's
    weights are asked for once."""
    embed = _served(top["embed"])
    scale = math.sqrt(cfg["hidden_size"]) if cfg.get("mup_enabled") else 1.0
    lens = [int(np.shape(ids)[1]) for ids in many_ids]
    xs = [jnp.take(embed, jnp.pad(jnp.asarray(ids)[0], (0, n - s)), axis=0)
          * scale for ids, s, n in zip(many_ids, lens, _padded(lens))]
    del embed
    margins = [jnp.full(x.shape[:1], jnp.inf, x.dtype) for x in xs]
    for i in range(cfg["num_hidden_layers"]):
        w = _served(layer_fn(i))
        for j, x in enumerate(xs):
            xs[j], margin = _layer_jit(w, x, jnp.int32(lens[j]),
                                       **_arch(cfg, i))
            margins[j] = jnp.minimum(margins[j], margin)
    return [jnp.concatenate([x, m[:, None]], -1)[None, :n]
            for x, m, n in zip(xs, margins, lens)]


def hidden_states(cfg, ids, layer_fn, top):
    return hidden_states_many(cfg, [ids], layer_fn, top)[0]


def _states(cfg, x):
    h = cfg["hidden_size"]
    return x[..., :h], x[..., h]


def _head(cfg, top):
    """The top as the dense decoder's reference takes it: what is said of a
    token at a position is that family's arithmetic on this family's
    logits."""
    top = _served(top)
    return ({"rms_norm_eps": cfg["rms_norm_eps"]},
            {"final_norm": top["final_norm"], "head": top["head"]})


def logits_of(cfg, x, top):
    """Logits [s, vocab] of one sequence's ``hidden_states_many``."""
    top = _served(top)
    with jax.default_matmul_precision(HIGHEST):
        return _rms(_states(cfg, x)[0], top["final_norm"],
                    float(cfg["rms_norm_eps"])) @ top["head"]


def token_stats(cfg, x, positions, tokens, top, temperature, top_p):
    """``reference/decoder.py``'s numbers about the given tokens of one
    sequence's ``hidden_states_many`` ``x`` [s, h + 1]. Where the
    position's route margin is under ``ROUTE_MARGIN``, ``best_gap`` reads 0
    and ``nucleus_gap`` minus infinity: no gap (module docstring)."""
    x, margin = _states(cfg, x)
    cfg, top = _head(cfg, top)
    got = dict(decoder.token_stats(cfg, x, positions, tokens, top,
                                   temperature, top_p))
    tie = margin[jnp.asarray(positions)] < ROUTE_MARGIN
    got["best_gap"] = jnp.where(tie, 0.0, got["best_gap"])
    got["nucleus_gap"] = jnp.where(tie, -jnp.inf, got["nucleus_gap"])
    return got


def draw_tokens(cfg, x, positions, top, temperature, top_p, key):
    """The reference as a sampler, for the controls."""
    x, _ = _states(cfg, x)
    cfg, top = _head(cfg, top)
    return decoder.draw_tokens(cfg, x, positions, top, temperature, top_p,
                               key)
