"""Scratch: SHA-256 digests of the seeded weights, as JSON on standard output.

Run from the root of a checkout, the parent's or this one:

    JAX_PLATFORMS=cpu python3 chipbench/scratch/weight_digests.py          # the test's cases
    python3 chipbench/scratch/weight_digests.py --full                     # on the chip

``chipbench/tests/data/weight_digests.json`` is the first form's output in a
checkout of the parent of PR 26 (commit 5009daa), whose ``harness/weights.py``
still named the dense decoder's leaves; ``tests/test_weights.py`` holds the
tree that stands to it. ``--full`` digests every leaf of both configurations
at full width, whole model and layer by layer: run on both sides in one chip
call, the two outputs have to be the same file.

It is the one script that knows both interfaces: the parent's entry points
took the configuration, this tree's take the family's leaf table.
"""

import hashlib
import json
import os
import sys

SEEDS = (7, 2 ** 31 + 77)


def digest(tree) -> str:
    """Names, types, shapes and bytes of every leaf, in the tree's order."""
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(f"{jax.tree_util.keystr(path)} {x.dtype} {x.shape}".encode())
        a = np.asarray(x)
        h.update(a.view(np.uint16 if a.dtype.itemsize == 2
                        else np.uint32).tobytes())
    return h.hexdigest()


def drawn_from(cell):
    """What the entry points of ``harness/weights.py`` take for this cell."""
    from chipbench.harness import weights as W

    if hasattr(W, "leaf_shapes"):          # the parent of PR 26
        return cell.config
    return cell.reference.leaf_table(cell.config)


def cases(cells: dict, full_width: dict) -> dict:
    """The test's digests: every entry point at the rehearsal size of each
    cell, two seeds; at full width the last layer and one top leaf alone."""
    import jax.numpy as jnp

    from chipbench.harness import weights as W

    out = {}
    for name, cell in cells.items():
        arg = drawn_from(cell)
        for seed in SEEDS:
            k = f"{name} rehearsal seed={seed}"
            model = W.model_weights(arg, seed)
            out[f"{k} model bf16"] = digest(model)
            for i in range(len(model["layers"])):
                out[f"{k} layer {i} f32"] = digest(W.layer_weights(arg, seed, i))
            out[f"{k} top f32"] = digest(W.top_weights(arg, seed))
            out[f"{k} top bf16"] = digest(
                W.top_weights(arg, seed, dtype=jnp.bfloat16))
    for name, cell in full_width.items():
        arg, seed = drawn_from(cell), SEEDS[1]
        last = cell.config["num_hidden_layers"] - 1
        out[f"{name} full width seed={seed} layer {last} bf16"] = digest(
            W.layer_weights(arg, seed, last, dtype=jnp.bfloat16))
        out[f"{name} full width seed={seed} final_norm f32"] = digest(
            W.top_weights(arg, seed, ("final_norm",)))
    return out


def full(cells: dict) -> dict:
    """Every leaf at full width: the whole model, and layer by layer."""
    import jax.numpy as jnp

    from chipbench.harness import weights as W

    out = {}
    for name, cell in cells.items():
        arg, seed = drawn_from(cell), SEEDS[1]
        model = W.model_weights(arg, seed)
        layers = model.pop("layers")
        out[f"{name} top of model bf16"] = digest(model)
        out[f"{name} top alone bf16"] = digest(
            W.top_weights(arg, seed, dtype=jnp.bfloat16))
        for i, lw in enumerate(layers):
            out[f"{name} layer {i} of model bf16"] = digest(lw)
            out[f"{name} layer {i} alone bf16"] = digest(
                W.layer_weights(arg, seed, i, dtype=jnp.bfloat16))
        del model, layers
    return out


def load_cells(rehearse: bool) -> dict:
    from chipbench.harness import loader

    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return {n: loader.load(n, rehearse=rehearse) for n in names}


def main(argv) -> int:
    if "--full" in argv:
        out = full(load_cells(False))
    else:
        out = cases(load_cells(True), load_cells(False))
    json.dump(out, sys.stdout, indent=1, sort_keys=True)
    print()
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())     # the checkout it is run from
    sys.exit(main(sys.argv[1:]))
