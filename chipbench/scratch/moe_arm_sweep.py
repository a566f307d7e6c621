#!/usr/bin/env python3
"""Scratch: the two arms of the dropless routed FFN against each other on the
chip, at the cell's widths (64 experts of 2048 x 1536, 4 a token), over the
row counts between the decode regime and the prefill pack:

    python3 chipbench/scratch/moe_arm_sweep.py [--rows 64,128,...] [--out f]

For each row count, each arm (the layer's own code, the arm forced through
the module's private threshold) runs ``--chain`` times inside ONE program
(``lax.fori_loop``, the output fed back), three timed calls after a warm one;
the line gives the median milliseconds a layer and the widest difference
between the two arms' outputs of one layer (they are the same mathematics).
Where ``sorted`` first beats ``dense`` is where ``_DENSE_ROWS`` belongs.
``--rehearse``: tiny widths on the CPU, to prove the script runs (its times
say nothing). PR 28's one chip call of it read 64 rows only (dense 1.668 ms,
sorted 3.761 ms a layer, the arms 0.0034 apart on outputs of rms 0.186) and
was cut at its time limit: that version closed over the experts' weights, so
every program held 1.2 GB of constants; this one hands them in as arguments
and has NOT run on the chip."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="64,128,192,256,384,512,1024")
    ap.add_argument("--chain", type=int, default=16)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models.moe import (SwiGLUExpertFFN,
                                                            dropless_ffn)
    from paddle_tpu.incubate.distributed.models.moe import moe_layer

    e, d, f, k = (8, 64, 32, 4) if args.rehearse else (64, 2048, 1536, 4)
    paddle.seed(28)
    experts = SwiGLUExpertFFN(e, d, f, dtype="bfloat16")
    tensors = [experts.w_gate, experts.w_up, experts.w_down]
    held = [t._data for t in tensors]
    lines = []
    for rows in (int(r) for r in args.rows.split(",")):
        key = jax.random.key(rows)
        tokens = jax.random.normal(key, (rows, d), jnp.bfloat16)
        scores = jax.random.uniform(jax.random.fold_in(key, 1), (rows, e))
        gates, idx = jax.lax.top_k(scores, k)
        gates = gates / gates.sum(-1, keepdims=True)
        line = {"rows": rows, "experts": e, "chain": args.chain}
        once = {}
        for arm, threshold in (("dense", 1 << 30), ("sorted", 0)):
            moe_layer._DENSE_ROWS = threshold       # read when traced

            # the weights go in as arguments: closed over, they would be
            # 1.2 GB of constants in the program (minutes to build one)
            def layer(x, weights):
                for t, w in zip(tensors, weights):
                    t._data = w
                return dropless_ffn(x, idx, gates, experts)[0]

            def chained(x, weights):
                def body(_, x):
                    y = layer(x, weights)
                    return x + y.astype(x.dtype) * jnp.bfloat16(1e-3)
                return jax.lax.fori_loop(0, args.chain, body, x)

            once[arm] = jax.jit(layer)(tokens, held).astype(jnp.float32)
            run = jax.jit(chained)
            run(tokens, held).block_until_ready()
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                run(tokens, held).block_until_ready()
                times.append((time.perf_counter() - t0) / args.chain * 1e3)
            for t, w in zip(tensors, held):
                t._data = w
            line[f"{arm}_ms"] = statistics.median(times)
        line["arms_differ_by"] = float(jnp.abs(once["dense"]
                                               - once["sorted"]).max())
        line["output_rms"] = float(jnp.sqrt(jnp.mean(once["dense"] ** 2)))
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            fh.writelines(json.dumps(l) + "\n" for l in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
