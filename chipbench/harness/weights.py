"""Seeded weights, made by the benchmark and handed to both sides.

The program's model and the plain reference must hold the same values, and
the reference may take nothing the program has made. So the benchmark makes
them: every leaf is ``normal * initializer_range`` (norm gains ``1 + 0.05 *
normal``) from a key folded from the seed, the layer and the leaf, rounded
to bfloat16 once. The whole model comes out of one jitted call on the
device; the reference asks for one layer at a time and gets the same bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: leaves of one decoder layer and of the model's top, in key order
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "head")


def leaf_shapes(cfg: dict) -> dict:
    """Shape of every leaf kind for a decoder configuration (HF keys)."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"attn_norm": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
            "wo": (q, h), "mlp_norm": (h,), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h), "embed": (v, h), "final_norm": (h,),
            "head": (h, v)}


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's are past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, shape, std: float, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.05 * x if len(shape) == 1 else std * x
    return x.astype(jnp.bfloat16).astype(dtype)


def _layer(key, i, shapes, std, dtype):
    k = jax.random.fold_in(key, i + 1)
    return {n: _leaf(jax.random.fold_in(k, j), shapes[n], std, dtype)
            for j, n in enumerate(LAYER_LEAVES)}


def _top(key, shapes, std, dtype, names=TOP_LEAVES):
    k = jax.random.fold_in(key, 0)
    return {n: _leaf(jax.random.fold_in(k, TOP_LEAVES.index(n)), shapes[n],
                     std, dtype) for n in names}


def _frozen(cfg: dict):
    return tuple(sorted((k, tuple(v)) for k, v in leaf_shapes(cfg).items()))


@functools.partial(jax.jit, static_argnames=("shapes", "n_layers", "std",
                                             "dtype"))
def _all(key, shapes, n_layers, std, dtype):
    sh = dict(shapes)
    out = dict(_top(key, sh, std, dtype))
    out["layers"] = [_layer(key, i, sh, std, dtype) for i in range(n_layers)]
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "std", "dtype"))
def _one_layer(key, i, shapes, std, dtype):
    return _layer(key, i, dict(shapes), std, dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "std", "dtype",
                                             "names"))
def _tops(key, shapes, std, dtype, names):
    return _top(key, dict(shapes), std, dtype, names)


def model_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model in one jitted call: ``{"embed", "final_norm",
    "head", "layers": [{leaf: array}]}``, in the type it is served in."""
    return _all(seed_key(seed), _frozen(cfg), cfg["num_hidden_layers"],
                float(cfg.get("initializer_range", 0.02)), dtype)


def layer_weights(cfg: dict, seed: int, i: int, dtype=jnp.float32) -> dict:
    """Layer ``i`` alone — the same bfloat16 values, upcast to ``dtype``."""
    return _one_layer(seed_key(seed), i, _frozen(cfg),
                      float(cfg.get("initializer_range", 0.02)), dtype)


def top_weights(cfg: dict, seed: int, names=TOP_LEAVES,
                dtype=jnp.float32) -> dict:
    return _tops(seed_key(seed), _frozen(cfg),
                 float(cfg.get("initializer_range", 0.02)), dtype,
                 tuple(names))
