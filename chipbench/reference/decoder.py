"""Plain reference of the decoder block both configurations share.

Pre-norm decoder as the InternLM2 and Mistral model cards describe it:
RMSNorm, grouped-query attention with rotary embeddings (half-rotated,
"NeoX" layout, theta from the config), SwiGLU feed-forward, untied head,
next-token cross entropy. Straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. It imports nothing of the program.

It runs a layer at a time, so that only one layer's float32 weights and one
layer's activations live on the device: the caller hands a function that
yields a layer's weights (made from the seed by ``harness/weights.py``).

Departures from the published code, each without effect on the mathematics:
the fused ``wqkv`` of InternLM2 is three matrices; weights are ``[in, out]``
and applied as ``x @ w``; rope scaling is left out (factor 1 in range).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"

#: leaves of one decoder layer and of the model's top, in key order
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "head")
#: the leaves drawn as gains (1 + 0.05 * normal); every other is std * normal
GAINS = ("attn_norm", "mlp_norm", "final_norm")


def leaf_shapes(cfg: dict) -> dict:
    """Shape of every leaf kind for a decoder configuration (HF keys)."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"attn_norm": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
            "wo": (q, h), "mlp_norm": (h,), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h), "embed": (v, h), "final_norm": (h,),
            "head": (h, v)}


def leaf_table(cfg: dict) -> dict:
    """What ``harness/weights.py`` draws for this family: every layer has
    the same leaves, ``(name, shape, kind)`` in key order."""
    shapes = leaf_shapes(cfg)
    leaves = lambda names: tuple(
        (n, shapes[n], "gain" if n in GAINS else "normal") for n in names)
    return {"std": float(cfg.get("initializer_range", 0.02)),
            "top": leaves(TOP_LEAVES),
            "layers": (leaves(LAYER_LEAVES),) * cfg["num_hidden_layers"]}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [b, s, heads, d] at positions 0..s-1, half-rotated layout."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def layer_forward(w, x, *, n_heads, n_kv, theta, eps, q_block=1024):
    """One decoder layer on x [b, s, h]; causal attention in query blocks so
    that the score matrix stays [b, heads, q_block, s]."""
    b, s, h = x.shape
    hd = w["wq"].shape[1] // n_heads
    y = _rms(x, w["attn_norm"], eps)
    q = _rope((y @ w["wq"]).reshape(b, s, n_heads, hd), theta)
    k = _rope((y @ w["wk"]).reshape(b, s, n_kv, hd), theta)
    v = (y @ w["wv"]).reshape(b, s, n_kv, hd)
    rep = n_heads // n_kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    outs = []
    for lo in range(0, s, q_block):
        hi = min(s, lo + q_block)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi])
        sc = sc / math.sqrt(hd)
        mask = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :hi]))
    a = jnp.concatenate(outs, 1).reshape(b, s, n_heads * hd)
    x = x + a @ w["wo"]
    y = _rms(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


def _arch(cfg):
    return dict(n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta",
                                             "eps"))
def _layer_jit(w, x, n_heads, n_kv, theta, eps):
    with jax.default_matmul_precision(HIGHEST):
        return layer_forward(w, x, n_heads=n_heads, n_kv=n_kv, theta=theta,
                             eps=eps)


def _position_logits(x, positions, g, head, eps):
    with jax.default_matmul_precision(HIGHEST):
        return _rms(x[positions], g, eps) @ head


def _nucleus(logits, temperature, top_p):
    """Plain nucleus sampling's kept set at each row: tokens in falling
    order of logit / temperature while the probability before them is at
    most ``top_p``. Returns the sorted token ids, the sorted scaled logits,
    their probabilities, the mass before each, and the kept mask."""
    lg = logits / temperature
    order = jnp.argsort(-lg, axis=-1)
    s = jnp.take_along_axis(lg, order, -1)
    p = jax.nn.softmax(s, -1)
    before = jnp.cumsum(p, -1) - p
    return order, s, p, before, before <= top_p


@functools.partial(jax.jit, static_argnames=("eps",))
def _stats_jit(x, positions, tokens, g, head, eps, temperature, top_p):
    logits = _position_logits(x, positions, g, head, eps)
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    _, s, p, before, keep = _nucleus(logits, temperature, top_p)
    # the lowest logit the reference's own nucleus keeps
    cut = jnp.min(jnp.where(keep, s, jnp.inf), -1) * temperature
    kept = jnp.sum(jnp.where(keep, p, 0.0), -1)
    # probability (at this temperature) of the tokens above the given one,
    # and what a sound sampler's draws average: sum p_t * before_t / kept
    above = jnp.sum(jnp.where(logits > got[:, None],
                              jax.nn.softmax(logits / temperature, -1), 0.0),
                    -1)
    expect = jnp.sum(jnp.where(keep, p * before, 0.0), -1) / kept
    return {"best_gap": jnp.max(logits, -1) - got,
            "nucleus_gap": cut - got, "mass_above": above,
            "mass_above_expected": expect}


@functools.partial(jax.jit, static_argnames=("eps",))
def _draw_jit(x, positions, g, head, eps, temperature, top_p, key):
    logits = _position_logits(x, positions, g, head, eps)
    order, s, _, _, keep = _nucleus(logits, temperature, top_p)
    pick = jax.random.categorical(key, jnp.where(keep, s, -jnp.inf), -1)
    drawn = jnp.take_along_axis(order, pick[:, None], -1)[:, 0]
    return jnp.argmax(logits, -1).astype(jnp.int32), drawn.astype(jnp.int32)


def hidden_states(cfg, ids, layer_fn, top):
    """Final hidden states [b, s, h] (before the last norm) of ``ids``."""
    return hidden_states_many(cfg, [ids], layer_fn, top)[0]


def hidden_states_many(cfg, many_ids, layer_fn, top):
    """The same for several inputs, layer by layer: each layer's weights
    are asked for once, so only one layer's live on the device."""
    xs = [jnp.take(top["embed"], jnp.asarray(ids), axis=0)
          for ids in many_ids]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_fn(i)
        xs = [_layer_jit(w, x, **_arch(cfg)) for x in xs]
    return xs


def token_stats(cfg, x, positions, tokens, top, temperature, top_p):
    """For one sequence's hidden states ``x`` [s, h]: at each of
    ``positions`` (whose logits predict the token one place on), about the
    given token: ``best_gap`` (how far its logit lies below the best),
    ``nucleus_gap`` (how far below the lowest logit that nucleus sampling at
    this temperature and top_p keeps; negative inside), ``mass_above`` (the
    probability of all tokens above it) and what that averages to under a
    sound sampler. Float32 [n] each. Callers pad to a few fixed lengths."""
    return _stats_jit(x, jnp.asarray(positions), jnp.asarray(tokens),
                      top["final_norm"], top["head"],
                      float(cfg["rms_norm_eps"]), jnp.float32(temperature),
                      jnp.float32(top_p))


def draw_tokens(cfg, x, positions, top, temperature, top_p, key):
    """The reference as a sampler, for the controls: at each position its
    first token and one drawn from its nucleus. int32 [n] each."""
    return _draw_jit(x, jnp.asarray(positions), top["final_norm"],
                     top["head"], float(cfg["rms_norm_eps"]),
                     jnp.float32(temperature), jnp.float32(top_p), key)


def loss_of(cfg, weights, ids):
    """Whole-model loss on ids [b, s] (labels are ids shifted by one), as one
    differentiable function of ``weights`` ({"embed","final_norm","head",
    "layers":[...]}): what ``jax.grad`` is taken of."""
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], ids, axis=0)
        for w in weights["layers"]:
            x = jax.checkpoint(functools.partial(layer_forward, **_arch(cfg))
                               )(w, x)
        x = _rms(x, weights["final_norm"], float(cfg["rms_norm_eps"]))
        h = x.shape[-1]
        # the head in row blocks, so that [rows, vocab] stays small
        xs = x[:, :-1].reshape(-1, h)
        ls = ids[:, 1:].reshape(-1)
        n = xs.shape[0]
        blk = 2048
        pad = (-n) % blk
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
        valid = jnp.pad(jnp.ones(n, jnp.float32), (0, pad))
        ls = jnp.pad(ls, (0, pad))
        def body(tot, a):
            xb, lb, vb = a
            return tot + jnp.sum(jax.checkpoint(
                lambda xb, lb: nll_rows(xb @ weights["head"], lb))(xb, lb)
                * vb), None
        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                              (xs.reshape(-1, blk, h), ls.reshape(-1, blk),
                               valid.reshape(-1, blk)))
        return tot / n


def nll_rows(logits, labels):
    return (jax.nn.logsumexp(logits, -1)
            - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])


# ---- the trainer's arithmetic, plainly -------------------------------------

def clip_by_global_norm(grads, clip: float):
    """Scale a pytree of gradients so that its global norm is at most
    ``clip``."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-6))
    return jax.tree_util.tree_map(lambda x: x * scale, grads)


def adamw_leaf(p, m, v, g, step, *, lr, b1, b2, eps, wd):
    """One AdamW step on one float32 leaf (``step`` counts from 1); weight
    decay on leaves of two or more dimensions only. Returns (p, m, v)."""
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    upd = (m / (1.0 - b1 ** step)) / (jnp.sqrt(v / (1.0 - b2 ** step)) + eps)
    return p - lr * (upd + (wd * p if p.ndim >= 2 else 0.0)), m, v
