#!/usr/bin/env python3
"""Read ROUTE_MARGIN's readings for a cell of the nemotron_h family (scratch,
never a run): for each sample dumped by ``lfm2_controls.py --dump`` (a sound
window's, an fp8 program's), one pass of the reference, then per served
token its route margin (``reference/nemotron_h.py``: pairs that involve a
held expert) beside the two gaps UNMASKED, written to
``chiprun_out/margins/<file>.npz`` and summed up per threshold: positions
kept, and the widest gaps among them.

    python3 chipbench/scratch/nemotron_h_margins.py <workload> <dump.json> ...

(``--rehearse`` among the files: the cell's tiny CPU preset.)
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from chipbench.harness import loader
from chipbench.harness import weights as W
from chipbench.reference import decoder

THRESHOLDS = (0.0, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05)


def main():
    paths = [a for a in sys.argv[2:] if a != "--rehearse"]
    cell = loader.load(sys.argv[1], rehearse="--rehearse" in sys.argv)
    cfg, ref, table = cell.config, cell.reference, cell.leaf_table
    s = cell.traffic["sampling"]
    h = cfg["hidden_size"]
    for path in paths:
        with open(path) as f:
            got = json.load(f)
        seed = got["seed"]
        top = W.top_weights(table, seed)
        rows = []
        for r in got["requests"]:
            ids = np.asarray(r["prompt"] + r["output"], np.int32)
            padded = np.zeros(-(-len(ids) // 512) * 512, np.int32)
            padded[:len(ids)] = ids
            rows.append((r, padded, len(r["prompt"]), len(r["output"])))
        xs = ref.hidden_states_many(
            cfg, [p[None] for _, p, _, _ in rows],
            lambda i: W.layer_weights(table, seed, i), top)
        dcfg, dtop = ref._head(cfg, top)
        out = {"margin": [], "best_gap": [], "nucleus_gap": [], "greedy": []}
        for (r, _, n_prompt, k), x in zip(rows, xs):
            x = x[0]
            pos = np.arange(n_prompt - 1, n_prompt - 1 + k)
            t, p = (1.0, 1.0) if r["greedy"] else (
                float(s["temperature"]), float(s["top_p"]))
            st = decoder.token_stats(dcfg, x[:, :h], pos,
                                     np.asarray(r["output"], np.int32), dtop,
                                     t, p)
            out["margin"].append(np.asarray(x[pos, h]))
            out["best_gap"].append(np.asarray(st["best_gap"]))
            out["nucleus_gap"].append(np.asarray(st["nucleus_gap"]))
            out["greedy"].append(np.full(k, r["greedy"]))
        out = {k: np.concatenate(v) for k, v in out.items()}
        name = os.path.splitext(os.path.basename(path))[0]
        os.makedirs("chiprun_out/margins", exist_ok=True)
        np.savez(os.path.join("chiprun_out/margins", name + ".npz"), **out)
        g = out["greedy"].astype(bool)
        for thr in THRESHOLDS:
            keep = out["margin"] >= thr
            kg, ks = keep & g, keep & ~g
            print(f"margins {name} >= {thr}: greedy kept {kg.sum()} of "
                  f"{g.sum()} max best_gap "
                  f"{out['best_gap'][kg].max() if kg.any() else 0:.4g}; "
                  f"sampled kept {ks.sum()} of {(~g).sum()} max nucleus_gap "
                  f"{max(0.0, out['nucleus_gap'][ks].max()) if ks.any() else 0:.4g}",
                  flush=True)


if __name__ == "__main__":
    main()
