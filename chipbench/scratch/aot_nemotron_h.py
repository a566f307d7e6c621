"""Rehearsal 3 for a cell of the nemotron_h family (scratch, never a run):
AOT-compile for a described v5e, at the cell's own sizes, the weight maker's
whole-model program, the decode block (donated: the state pools are updated
in place), the widest packed prefill chunk (with each row's slot and its
count of kept positions, as an engine over "seq" layers passes them) and the
widest first-token program. Prints compile seconds and memory_analysis() of
each, or the compiler's refusal.
  python chipbench/scratch/aot_nemotron_h.py <workload> [what ...]
``what``: weights mega chunk first (default: all); ``text`` also writes the
decode block's and the chunk's HLO to chiprun_out/aot_nemotron_{mega,chunk}.txt."""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench.harness import loader, serving
from chipbench.harness import weights as W


def main():
    cell = loader.load(sys.argv[1])
    what = sys.argv[2:] or ["weights", "mega", "chunk", "first"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one)
    tree = lambda t: jax.tree_util.tree_map(sds, t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)

    def report(name, fn, *args, **kw):
        t0 = time.time()
        try:
            c = fn.trace(*args, **kw).lower(
                lowering_platforms=("tpu",)).compile()
            ma = c.memory_analysis()
            print(f"OK {name}: compile {time.time() - t0:.0f}s, arguments "
                  f"{ma.argument_size_in_bytes / 1e9:.2f} GB, temps "
                  f"{ma.temp_size_in_bytes / 1e9:.2f} GB, outputs-aliased "
                  f"{(ma.output_size_in_bytes - ma.alias_size_in_bytes) / 1e9:.2f}"
                  f" GB, aliased {ma.alias_size_in_bytes / 1e9:.2f} GB",
                  flush=True)
            return c
        except Exception as e:
            print(f"REFUSED {name} after {time.time() - t0:.0f}s: "
                  f"{str(e)[:1500]}", flush=True)

    if "weights" in what:
        t = cell.leaf_table
        report("weights, whole model", W._all, sds(W.seed_key(1)),
               top=W._frozen(t["top"]),
               layers=tuple(W._frozen(l) for l in t["layers"]),
               std=float(t["std"]), dtype=jnp.bfloat16)
    e = cell.spec["engine"]
    model = cell.adapter.build_model(cell.config,
                                     max_positions=int(e["max_len"]))
    eng = serving.build_engine(cell, model)
    params = tree(eng._params)
    kv = tree(eng.caches["kv"])
    tables = sds(eng.caches["tables"])
    B, P, C = eng.max_batch, eng._maxp, eng._chunk_tokens
    print(f"engine: seq_state_bytes {eng.stats['seq_state_bytes'] / 1e9:.3f}"
          f" GB, kv layers {eng.stats['kv_layers']} (kernel "
          f"{eng.stats['paged_kernel_layers']}, page-append "
          f"{eng.stats['page_append_layers']})", flush=True)
    if "mega" in what:
        mega = eng._build_mega_jit()
        act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)
        c = report(f"mega n={eng.block_size} sampled", mega, params, i32(B),
                   kv, tables, i32(B), act, i32(B), f32(B), f32(B), i32(B),
                   n_steps=eng.block_size, do_sample=True)
        if c is not None and "text" in what:
            out = os.path.join("chiprun_out", "aot_nemotron_mega.txt")
            os.makedirs("chiprun_out", exist_ok=True)
            open(out, "w").write(c.as_text())
            print("  HLO ->", out)
    g = 1
    while g < eng._pack_rows:
        g *= 2
    if "chunk" in what:
        c = report(f"chunk g={g} x {C}", eng._chunk_fn(g), params, i32(g, C),
                   kv, i32(g, P), i32(g), i32(g), i32(g))
        if c is not None and "text" in what:
            out = os.path.join("chiprun_out", "aot_nemotron_chunk.txt")
            os.makedirs("chiprun_out", exist_ok=True)
            open(out, "w").write(c.as_text())
            print("  HLO ->", out)
    if "first" in what:
        # the first-token program is built inside _first_token: compile the
        # same body through the model's hook
        from paddle_tpu.core import autograd_engine
        from paddle_tpu.jit.api import _Swap

        def first(params, last, kv, rows, true_len, slots):
            sub = {"kv": kv, "tables": rows, "seq_slots": slots}
            with autograd_engine.no_grad(), _Swap(eng._tensors, params):
                logits, sub = eng.model.paged_token_step(last, sub,
                                                         true_len - 1)
            return logits, sub["kv"]

        report(f"first-token step g={B}", jax.jit(first, donate_argnums=2),
               params, i32(B), kv, i32(B, P), i32(B), i32(B))


if __name__ == "__main__":
    main()
