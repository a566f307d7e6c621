"""Plain reference of the Nemotron-H block (``model_type`` ``nemotron_h``).

The equations of the published modelling code, in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
chunked scan, no batching tricks. It imports nothing of the program (the
nucleus arithmetic on a position's logits is ``reference/decoder.py``'s).

``x`` is a block's input [s, hidden]; ``u = RMSNorm(x; norm, eps)``,
``y = x + mixer(u)``, ONE mixer a block, its kind a letter of
``hybrid_override_pattern``; after the last block ``RMSNorm(.; final_norm)``
and the untied ``head``. No positional embedding anywhere
(``rope_theta`` and ``partial_rotary_factor`` are in the published config and
the family's modelling code applies no rotary).

  M  Mamba-2. ``H`` = ``mamba_num_heads`` heads of ``P`` =
     ``mamba_head_dim``, ``d_inner = H P`` (NOT ``expand`` x hidden: the
     family's code never reads ``expand``), ``G`` = ``n_groups``, ``N`` =
     ``ssm_state_size``. ``[z | xBC | dt] = u w_in``;
     ``xBC_t = silu(conv_b + sum_k conv_w[:, k] xBC_{t-3+k})``, zeros
     before the start; split ``x_t`` [H, P], ``B_t``, ``C_t`` [G, N], head
     ``h`` reading group ``h // (H / G)``; ``dt_t = softplus(dt_t +
     dt_bias)``, no clamp; ``A = -exp(A_log)`` (the seeded ``A_log`` and
     ``dt_bias`` are added to the family's initialisation and the conv
     taps scaled, ``on_family_init``: ``A`` in 1-16, ``dt`` in
     ``time_step_min..max``, taps of 0.08, so that the state matters and
     lasts from under one position to a thousand). THE PLAIN RECURRENCE, a
     position at a time: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``
     (``S`` [H, P, N], ``S_{-1} = 0``), ``y_t = S_t C_t + D x_t``. Then
     ``GroupRMSNorm(y * silu(z); ssm_norm, groups of d_inner / G)`` (gate
     first, then norm) and ``w_out``.
  *  attention. q (heads x head_dim), k, v (kv heads x head_dim), no bias,
     no rotary, no QK-norm; causal ``softmax(q k^T / sqrt(head_dim)) v``;
     ``wo``.
  E  experts, in float32. ``s = sigmoid(u router)`` over the PUBLISHED
     number of experts; the ``num_experts_per_tok`` chosen are the largest
     of ``s + e_score_correction_bias`` (``n_group = topk_group = 1``: no
     group limit); gates ``s[chosen] / (sum s[chosen] + 1e-20)`` times
     ``routed_scaling_factor``. Expert ``e``: ``relu(u up_e)^2 down_e``; the
     shared expert the same form on every token. The output is the gated
     sum over the chosen experts HELD HERE (``n_routed_experts`` of the
     configuration as it is run, ``[first, first + held)``) plus the shared
     expert, whole: one chip's share of an expert-parallel layer. What the
     absent experts would add is left out, and that partial result goes on.
     Every held expert is multiplied for every position, the unchosen
     weighted 0: 16 experts are cheap enough for that to stay plain.

It runs a layer at a time, on weights rounded to bfloat16's values
(``_served``, as ``reference/lfm2.py`` explains).

Where the choice of experts is all but a tie, a bfloat16 program and this
reference each choose rightly and differ by a whole expert's output. Here
only a flip that involves an expert HELD HERE changes the result by a step
(a flip between two absent experts moves the held ones' gates through their
common divisor, by about a hundredth), so ``route`` gives as a position's
margin the least gap in ``s + bias`` between a chosen and an unchosen expert
over the pairs of which at least one is held (infinite where no such pair
exists), ``hidden_states_many`` the least of that over the expert layers,
and ``token_stats`` reads the two maxima (``best_gap``, ``nucleus_gap``)
only where it is ``ROUTE_MARGIN`` or more; the mean (``mass_above``) is read
everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import decoder

HIGHEST = "highest"
MAMBA, ATTN, MOE = "M", "*", "E"
#: the least margin (module docstring) at which the reference's choice of
#: experts is taken for the only right one. Read on the chip with every
#: leaf drawn at 0.02 (PR 36, call 1): 10.7% of a sound window's tokens lie
#: at 0.005 or more; at 0.01 1% are left (37 greedy tokens), at 0.002 a
#: sound window read 0.67 against 0.025-0.394 at 0.005. Kept under
#: ``on_family_init``'s weights (fix session, call 52: sound 0.159-0.967
#: over eight windows, fp8 weights 2.50, a lost state 4.5-4.8; the cell's
#: ``limits_from`` has the table)
ROUTE_MARGIN = 0.005


def _dims(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = heads * p
    return dict(heads=heads, p=p, g=g, n=n, d_inner=d_inner,
                width=d_inner + 2 * g * n)


def family_init(cfg):
    """``(A_log, dt_bias)`` [H] float32 of the family's own initialisation,
    spread evenly over the heads in place of drawn (as the program's
    ``NemotronHMamba2`` does): ``A`` from 1 to 16, ``dt`` from
    ``time_step_min`` to ``time_step_max`` on a log scale, ``dt_bias`` its
    inverse softplus. Head 0 keeps a state for about 1 / (A dt) = 1,000
    positions, the last head for under one."""
    heads = int(cfg["mamba_num_heads"])
    dt = np.exp(np.linspace(math.log(float(cfg.get("time_step_min", 1e-3))),
                            math.log(float(cfg.get("time_step_max", 0.1))),
                            heads))
    return (np.log(np.linspace(1.0, 16.0, heads)).astype(np.float32),
            (dt + np.log(-np.expm1(-dt))).astype(np.float32))


#: the std the conv taps are brought to (``conv_scale``)
TAP_STD = 0.08


def conv_scale(cfg) -> float:
    """The power of two that brings a tap drawn at the table's std nearest
    ``TAP_STD`` (0.02 x 4); a power of two, so that bfloat16 holds the
    product exactly."""
    return 2.0 ** round(math.log2(
        TAP_STD / float(cfg.get("initializer_range", 0.02))))


def on_family_init(cfg, w):
    """A Mamba-2 layer's leaves as the MODEL holds them; ``mamba_op`` here
    and the adapter's ``assign`` in the program both go through this.

    The leaf table draws every leaf at one std, 0.02. A Mamba-2 layer
    drawn so keeps no memory that matters: ``A`` is about -1 and ``dt``
    about 0.7, so a state forgets within a few positions, and taps of 0.02
    make ``x``, ``B`` and ``C`` some 0.02, so the state's term of ``y``
    (their cube) lies 10-50 times UNDER the skip ``D x``: a state zeroed
    every 32 positions moves the logits by 0.06 on average, a twentieth of
    their spread, whatever ``A`` and ``dt`` are (PERF.md section 6, PR 36:
    the readings behind every number here). So ``A_log`` and ``dt_bias``
    are ADDED to the family's own initialisation (``family_init``) and the
    taps are multiplied by ``conv_scale``, 4: the state's term then is
    about as large as the skip, lasts up to a thousand positions, and one
    zeroing moves a served token by 0.9-1.6 where the sound bfloat16
    program reads 0.23. The family's own scales throughout (taps of 0.3,
    ``D`` = 1) would make the state matter as well, but there the sound
    program's own rounding reads 0.65 against a lost state's 1.4-1.9."""
    a_log, dt_bias = family_init(cfg)
    return dict(w, A_log=a_log + w["A_log"], dt_bias=dt_bias + w["dt_bias"],
                conv_w=conv_scale(cfg) * w["conv_w"])


def routed_experts(cfg) -> int:
    """The router's width: the published number of experts (``published``
    holds it where ``n_routed_experts`` gives what is held here)."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def leaf_table(cfg: dict) -> dict:
    """What ``harness/weights.py`` draws: ``(name, shape, kind)`` of every
    leaf, a layer's table by its letter. ``A_log``, ``dt_bias``, ``D``, the
    conv bias and the router's correction bias are "normal" leaves (no
    gains; what the model holds of ``A_log``, ``dt_bias`` and the taps is
    ``on_family_init`` of them, on both sides); the norms are gains."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    d = _dims(cfg)
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    held, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    norm = ("norm", (h,), "gain")
    kinds = {
        MAMBA: (norm,
                ("w_in", (h, d["d_inner"] + d["width"] + d["heads"]),
                 "normal"),
                ("conv_w", (d["width"], cfg["conv_kernel"]), "normal"),
                ("conv_b", (d["width"],), "normal"),
                ("dt_bias", (d["heads"],), "normal"),
                ("A_log", (d["heads"],), "normal"),
                ("D", (d["heads"],), "normal"),
                ("ssm_norm", (d["d_inner"],), "gain"),
                ("w_out", (d["d_inner"], h), "normal")),
        ATTN: (norm, ("wq", (h, q), "normal"), ("wk", (h, kv), "normal"),
               ("wv", (h, kv), "normal"), ("wo", (q, h), "normal")),
        MOE: (norm, ("router", (h, routed_experts(cfg)), "normal"),
              ("e_score_correction_bias", (routed_experts(cfg),), "normal"),
              ("experts_up", (held, h, fe), "normal"),
              ("experts_down", (held, fe, h), "normal"),
              ("shared_up", (h, fs), "normal"),
              ("shared_down", (fs, h), "normal")),
    }
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not name "
                         "num_hidden_layers layers")
    return {"std": float(cfg.get("initializer_range", 0.02)),
            "top": (("embed", (v, h), "normal"),
                    ("final_norm", (h,), "gain"),
                    ("head", (h, v), "normal")),
            "layers": tuple(kinds[letter] for letter in pattern)}


@jax.jit
def _served(weights):
    """Every leaf rounded to bfloat16's values, in float32, written as
    ``reduce_precision``, which no compiler folds away."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                           mantissa_bits=7), weights)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mamba_op(w, u, *, heads, p, g, n, eps, dt_min=1e-3, dt_max=0.1,
             std=0.02):
    """The Mamba-2 mixer on u [s, h], the recurrence a position at a
    time; ``w`` the seeded leaves (``on_family_init`` is applied here)."""
    w = on_family_init({"mamba_num_heads": heads, "time_step_min": dt_min,
                        "time_step_max": dt_max, "initializer_range": std},
                       w)
    s = u.shape[0]
    d_inner, width = heads * p, heads * p + 2 * g * n
    z, xbc, dt = jnp.split(u @ w["w_in"], [d_inner, d_inner + width], axis=-1)
    taps = w["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, width), xbc.dtype), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][:, k] * padded[k:k + s] for k in range(taps)))
    x, b_, c_ = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    x = x.reshape(s, heads, p)
    b_ = jnp.repeat(b_.reshape(s, g, n), heads // g, axis=1)    # [s, H, N]
    c_ = jnp.repeat(c_.reshape(s, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # [s, H]
    a = -jnp.exp(w["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.sum(state * c_t[:, None, :], -1) + w["D"][:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), u.dtype),
                        (x, b_, c_, dt))
    y = (y.reshape(s, d_inner) * jax.nn.silu(z)).reshape(s, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y.reshape(s, d_inner) * w["ssm_norm"]) @ w["w_out"]


def attention_op(w, u, *, n_heads, n_kv, q_block=1024):
    """Causal grouped-query attention on u [s, h], in query blocks so that
    the score matrix stays [heads, q_block, s]."""
    s = u.shape[0]
    hd = w["wq"].shape[1] // n_heads
    q = (u @ w["wq"]).reshape(s, n_heads, hd)
    k = (u @ w["wk"]).reshape(s, n_kv, hd)
    v = (u @ w["wv"]).reshape(s, n_kv, hd)
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


def route(w, u, *, k, renorm, scaling, first, held):
    """(chosen experts [s, k], gates [s, k], margin [s]) of u [s, h]. The
    margin: the least gap in ``s + bias`` between a chosen and an unchosen
    expert over the pairs of which at least one is in ``[first, first +
    held)``; infinite where there is no such pair."""
    s = jax.nn.sigmoid(u @ w["router"])
    pick = s + w["e_score_correction_bias"]
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


def experts_op(w, u, idx, g, *, first):
    """The gated sum over the chosen experts among the held
    ``[first, first + held)``: every held expert for every position, the
    unchosen weighted 0."""
    held = w["experts_up"].shape[0]
    local = idx - first
    gate_of = jnp.sum(jnp.where(
        local[:, :, None] == jnp.arange(held)[None, None, :],
        g[:, :, None], 0.0), axis=1)                            # [s, held]
    act = jnp.square(jax.nn.relu(
        jnp.einsum("sd,edf->sef", u, w["experts_up"]))) * gate_of[:, :, None]
    return jnp.einsum("sef,efd->sd", act, w["experts_down"])


def shared_op(w, u):
    return jnp.square(jax.nn.relu(u @ w["shared_up"])) @ w["shared_down"]


def layer_forward(w, x, *, kind, eps, mamba, n_heads, n_kv, k, renorm,
                  scaling, first):
    """One block on x [s, h]: (y [s, h], the router's margin [s], infinite
    in a block without a router)."""
    u = _rms(x, w["norm"], eps)
    no_margin = jnp.full(x.shape[:1], jnp.inf, x.dtype)
    if kind == MAMBA:
        return x + mamba_op(w, u, eps=eps, **dict(mamba)), no_margin
    if kind == ATTN:
        return x + attention_op(w, u, n_heads=n_heads, n_kv=n_kv), no_margin
    idx, g, margin = route(w, u, k=k, renorm=renorm, scaling=scaling,
                           first=first, held=w["experts_up"].shape[0])
    return x + experts_op(w, u, idx, g, first=first) + shared_op(w, u), margin


def _arch(cfg, i):
    d = _dims(cfg)
    return dict(kind=cfg["hybrid_override_pattern"][i],
                eps=float(cfg["layer_norm_epsilon"]),
                mamba=tuple((key, d[key]) for key in ("heads", "p", "g", "n"))
                + (("dt_min", float(cfg.get("time_step_min", 1e-3))),
                   ("dt_max", float(cfg.get("time_step_max", 0.1))),
                   ("std", float(cfg.get("initializer_range", 0.02)))),
                n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"],
                k=cfg["num_experts_per_tok"],
                renorm=bool(cfg["norm_topk_prob"]),
                scaling=float(cfg["routed_scaling_factor"]),
                first=0)           # the configurations hold experts [0, held)


@functools.partial(jax.jit, static_argnames=(
    "kind", "eps", "mamba", "n_heads", "n_kv", "k", "renorm", "scaling",
    "first"))
def _layer_jit(w, x, **arch):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(w, x, **arch)


def hidden_states_many(cfg, many_ids, layer_fn, top):
    """For each ``ids`` [1, s]: [1, s, h + 1], the final hidden states
    (before the last norm) and, in the last column, the position's route
    margin (the least over its expert layers). Layer by layer: each layer's
    weights are asked for once."""
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
    h = cfg["hidden_size"]
    return x[..., :h], x[..., h]


def _head(cfg, top):
    """The top as the dense decoder's reference takes it: what is said of a
    token at a position is that family's arithmetic on this family's
    logits."""
    top = _served(top)
    return ({"rms_norm_eps": cfg["layer_norm_epsilon"]},
            {"final_norm": top["final_norm"], "head": top["head"]})


def logits_of(cfg, x, top):
    """Logits [s, vocab] of one sequence's ``hidden_states_many``."""
    top = _served(top)
    with jax.default_matmul_precision(HIGHEST):
        return _rms(_states(cfg, x)[0], top["final_norm"],
                    float(cfg["layer_norm_epsilon"])) @ top["head"]


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
