"""Seeded weights, made by the benchmark and handed to both sides.

The program's model and the plain reference must hold the same values, and
the reference may take nothing the program has made. So the benchmark makes
them, and knows no family: which leaves there are is the family's to say.
The reference a configuration names states them, ``leaf_table(cfg)``:

    {"std": 0.02, "top": leaves, "layers": [leaves of layer 0, ...]}

where leaves are ``(name, shape, kind)`` in the family's order and ``kind``
says how the leaf is drawn: "normal" (``std * normal``) or "gain" (``1 +
0.05 * normal``). Layers may differ from one another, a leaf has any rank.

The benchmark's own is the rest: a key folded from the seed, the place (0
the top, ``i + 1`` layer ``i``) and the leaf's index in that place's order;
one rounding to bfloat16; the whole model out of one jitted call on the
device, one place out of another — the reference asks for a layer at a time
and gets the same bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

KINDS = ("normal", "gain")


def seed_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's are past 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _frozen(leaves) -> tuple:
    """One place's leaves as a static argument; a table that cannot be drawn
    is refused here."""
    out = tuple((str(n), tuple(int(d) for d in shape), str(kind))
                for n, shape, kind in leaves)
    names = [n for n, _, _ in out]
    if len(set(names)) != len(names) or "layers" in names:
        raise ValueError(f"leaf table: names {names} repeat, or one is "
                         f"'layers'")
    if any(kind not in KINDS for _, _, kind in out):
        raise ValueError(f"leaf table: a kind is none of {KINDS}: {out}")
    return out


def _draw(key, place, leaves, std, dtype, names=None):
    k = jax.random.fold_in(key, place)
    out = {}
    for j, (name, shape, kind) in enumerate(leaves):
        if names is None or name in names:
            x = jax.random.normal(jax.random.fold_in(k, j), shape, jnp.float32)
            x = 1.0 + 0.05 * x if kind == "gain" else std * x
            out[name] = x.astype(jnp.bfloat16).astype(dtype)
    return out


@functools.partial(jax.jit, static_argnames=("top", "layers", "std", "dtype"))
def _all(key, top, layers, std, dtype):
    out = _draw(key, 0, top, std, dtype)
    out["layers"] = [_draw(key, i + 1, leaves, std, dtype)
                     for i, leaves in enumerate(layers)]
    return out


_one = jax.jit(_draw, static_argnames=("leaves", "std", "dtype", "names"))


def model_weights(table: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf of the model in one jitted call: the top's leaves by name
    and ``"layers": [{leaf: array}]``, in the type it is served in."""
    return _all(seed_key(seed), _frozen(table["top"]),
                tuple(_frozen(leaves) for leaves in table["layers"]),
                float(table["std"]), dtype)


def layer_weights(table: dict, seed: int, i: int, dtype=jnp.float32) -> dict:
    """Layer ``i`` alone — the same bfloat16 values, upcast to ``dtype``."""
    return _one(seed_key(seed), i + 1, _frozen(table["layers"][i]),
                float(table["std"]), dtype)


def top_weights(table: dict, seed: int, names=None,
                dtype=jnp.float32) -> dict:
    """The top's leaves, or those of them that ``names`` lists."""
    return _one(seed_key(seed), 0, _frozen(table["top"]),
                float(table["std"]), dtype,
                None if names is None else tuple(names))
