"""``harness/weights.py`` knows no family: (a) for the family the benchmark
has it draws the bits it drew when it named that family's leaves itself,
(b) a made-up second family is a table and nothing else, (c) nothing under
``harness/`` names a leaf or reads a width."""

import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import loader
from chipbench.harness import weights as W

SEED = 2 ** 31 + 77


# ---- (a) the parent's bits ---------------------------------------------------

with open(os.path.join(os.path.dirname(__file__), "data",
                       "weight_digests.json")) as _f:
    PARENT = json.load(_f)       # scratch/weight_digests.py, run at 5009daa


@pytest.fixture(scope="module")
def digests():
    rec = loader._module("scratch", "weight_digests", "the recorder")
    return rec.cases(rec.load_cells(True), rec.load_cells(False))


def test_the_parent_recorded_every_case(digests):
    assert set(digests) == set(PARENT) and len(PARENT) >= 20


@pytest.mark.parametrize("case", sorted(PARENT))
def test_same_seed_same_bits_as_the_parent(digests, case):
    assert digests[case] == PARENT[case]


# ---- (b) a made-up second family, as a table ---------------------------------

MADE_UP = {"hidden": 32, "dense_ffn": 64, "experts": 4, "expert_ffn": 16,
           "vocab": 128, "positions": 24, "depth": 3, "init_std": 0.1}


def leaf_table(cfg):
    """What a reference of this family would state: a dense first layer,
    then layers with a router, stacked experts and a router bias (1-D and
    no gain); learned positions beside embed, final_norm and head."""
    h, f, e, g = (cfg[k] for k in ("hidden", "dense_ffn", "experts",
                                   "expert_ffn"))
    attn = (("attn_norm", (h,), "gain"), ("wqkv", (h, 3 * h), "normal"),
            ("wo", (h, h), "normal"), ("mlp_norm", (h,), "gain"))
    dense = attn + (("w_up", (h, f), "normal"), ("w_down", (f, h), "normal"))
    routed = attn + (("router", (h, e), "normal"),
                     ("router_bias", (e,), "normal"),
                     ("experts_up", (e, h, g), "normal"),
                     ("experts_down", (e, g, h), "normal"))
    return {"std": cfg["init_std"],
            "top": (("embed", (cfg["vocab"], h), "normal"),
                    ("pos_embed", (cfg["positions"], h), "normal"),
                    ("final_norm", (h,), "gain"),
                    ("head", (h, cfg["vocab"]), "normal")),
            "layers": (dense,) + (routed,) * (cfg["depth"] - 1)}


@pytest.fixture(scope="module")
def made_up():
    table = leaf_table(MADE_UP)
    return table, W.model_weights(table, SEED)


def _bits(x):
    return np.asarray(x.astype(jnp.bfloat16)).view(np.uint16)


def test_every_leaf_of_the_table_has_its_name_shape_and_type(made_up):
    table, model = made_up
    assert set(model) == {n for n, _, _ in table["top"]} | {"layers"}
    assert len(model["layers"]) == MADE_UP["depth"]
    for leaves, got in [(table["top"], model)] + list(
            zip(table["layers"], model["layers"])):
        assert set(got) - {"layers"} == {n for n, _, _ in leaves}
        for name, shape, _ in leaves:
            assert got[name].shape == shape, name
            assert got[name].dtype == jnp.bfloat16
    assert model["layers"][1]["experts_up"].ndim == 3


def test_layers_follow_their_own_tables(made_up):
    _, model = made_up
    first, second, third = model["layers"]
    assert "w_up" in first and "router" not in first
    assert "router" in second and "w_up" not in second
    assert set(second) == set(third)


def test_the_kind_is_the_table_s_and_not_the_rank_s(made_up):
    _, model = made_up
    top = W.top_weights(leaf_table(dict(MADE_UP, hidden=4096)), SEED,
                        ("final_norm",))
    gain = np.asarray(top["final_norm"], np.float64)
    assert abs(gain.mean() - 1.0) < 0.01 and abs(gain.std() - 0.05) < 0.005
    # a 1-D leaf that is no gain is std * normal like any matrix
    bias = leaf_table(MADE_UP)
    bias["layers"] = ((("router_bias", (4096,), "normal"),),)
    b = np.asarray(W.layer_weights(bias, SEED, 0)["router_bias"], np.float64)
    assert abs(b.mean()) < 0.01 and abs(b.std() - MADE_UP["init_std"]) < 0.01
    experts = np.asarray(model["layers"][1]["experts_up"], np.float64)
    assert abs(experts.std() - MADE_UP["init_std"]) < 0.01


def test_same_seed_same_bits_and_another_seed_others(made_up):
    table, model = made_up
    again = W.model_weights(leaf_table(MADE_UP), SEED)
    other = W.model_weights(table, SEED + 1)
    for name in ("embed", "pos_embed"):
        assert (_bits(model[name]) == _bits(again[name])).all()
        assert (_bits(model[name]) != _bits(other[name])).any()
    a, b = model["layers"][1], again["layers"][1]
    assert all((_bits(a[n]) == _bits(b[n])).all() for n in a)
    # every place and every leaf has a key of its own
    assert (_bits(model["layers"][1]["router"])
            != _bits(model["layers"][2]["router"])).any()
    assert (_bits(a["attn_norm"]) != _bits(a["mlp_norm"])).any()


def test_a_layer_or_some_top_leaves_alone_are_the_whole_model_s_bits(made_up):
    table, model = made_up
    for i, want in enumerate(model["layers"]):
        got = W.layer_weights(table, SEED, i)
        assert set(got) == set(want)
        for name in want:
            assert got[name].dtype == jnp.float32
            assert (_bits(got[name]) == _bits(want[name])).all(), (i, name)
            # float32 holds the bfloat16 value, no more
            assert (got[name] == want[name].astype(jnp.float32)).all()
    whole = W.top_weights(table, SEED)
    assert set(whole) == {n for n, _, _ in table["top"]}
    some = W.top_weights(table, SEED, ("head", "pos_embed"),
                         dtype=jnp.bfloat16)
    assert set(some) == {"head", "pos_embed"}
    for name in whole:
        assert (_bits(whole[name]) == _bits(model[name])).all(), name
    for name in some:
        assert (_bits(some[name]) == _bits(model[name])).all(), name


@pytest.mark.parametrize("leaves", [
    (("a", (2,), "normal"), ("a", (3,), "normal")),      # a name twice
    (("layers", (2,), "normal"),),                       # the tree's own key
    (("a", (2,), "uniform"),),                           # no such kind
])
def test_a_table_that_cannot_be_drawn_is_refused(leaves):
    table = {"std": 0.02, "top": leaves, "layers": ()}
    with pytest.raises(ValueError, match="leaf table"):
        W.model_weights(table, 1)


# ---- (c) the harness names no leaf and reads no width ------------------------

def test_nothing_under_harness_names_a_leaf_or_reads_a_width():
    from chipbench.reference import decoder

    names = set(decoder.LAYER_LEAVES + decoder.TOP_LEAVES)
    table = leaf_table(MADE_UP)
    names |= {n for leaves in (table["top"],) + table["layers"]
              for n, _, _ in leaves}
    assert len(names) >= 18
    banned = re.compile(r"(?<![A-Za-z0-9_])(" + "|".join(
        sorted(names | {"intermediate_size", "hidden_size", "head_dim"}))
        + r")(?![A-Za-z0-9_])")
    folder = os.path.join(loader.HERE, "harness")
    files = [os.path.join(folder, f) for f in sorted(os.listdir(folder))
             if os.path.isfile(os.path.join(folder, f))]
    files += [os.path.join(loader.HERE, f) for f in ("control.py", "run.py")]
    assert len(files) >= 11
    found = {}
    for path in files:
        with open(path) as f:
            hits = sorted(set(banned.findall(f.read())))
        if hits:
            found[os.path.relpath(path, loader.HERE)] = hits
    assert not found


def test_the_cell_hands_the_harness_its_reference_s_table():
    cell = loader.load("mistral-7b.train-4k", rehearse=True)
    table = cell.leaf_table
    assert table == cell.reference.leaf_table(cell.config)
    assert len(table["layers"]) == cell.config["num_hidden_layers"]
    kinds = {n: k for n, _, k in table["top"] + table["layers"][0]}
    assert {n for n, k in kinds.items() if k == "gain"} == {
        "attn_norm", "mlp_norm", "final_norm"}
