"""Rehearsal for a cell of the afmoe family (scratch, never a run):
AOT-compile for a described v5e, at the cell's own sizes, the decode block
(donated), the packed prefill chunk at the widest row count the cell can
meet (every slot mid-prefill at once) and at ``pack_rows``, and the widest
first-token program; the tables are one a page group. Prints compile seconds
and memory_analysis() of each, or the compiler's refusal, and the pools'
bytes by group.
  python chipbench/scratch/aot_afmoe.py <workload> [what ...]
``what``: mega chunk first (default: all); ``text`` also writes the decode
block's HLO to chiprun_out/aot_afmoe_mega.txt."""

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


def main():
    cell = loader.load(sys.argv[1])
    what = sys.argv[2:] or ["mega", "chunk", "first"]
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

    e = cell.spec["engine"]
    model = cell.adapter.build_model(cell.config,
                                     max_positions=int(e["max_len"]))
    eng = serving.build_engine(cell, model)
    params = tree(eng._params)
    kv = tree(eng.caches["kv"])
    tables = tree(eng.caches["tables"])
    B, P, C = eng.max_batch, eng._maxp, eng._chunk_tokens
    rows = lambda g: jax.tree_util.tree_map(lambda _: i32(g, P), tables)
    by_group = {}
    for (k, v), g in zip(eng.caches["kv"], eng._layer_groups):
        by_group[g] = by_group.get(g, 0) + k.nbytes + v.nbytes
    print("engine: " + ", ".join(
        f"group {g.kind} (window {g.window}) {g.num_blocks} pages, "
        f"{g.slot_pages} a slot, {by_group[i] / 1e9:.3f} GB"
        for i, g in enumerate(eng._groups.groups))
        + f"; parameters {sum(np.prod(p.shape) for p in eng._params) / 1e9:.3f}"
        f" B; kernel layers {eng.stats['paged_kernel_layers']} of "
        f"{eng.stats['kv_layers']}, page-append "
        f"{eng.stats['page_append_layers']}; chunk {C}, pack_rows "
        f"{eng._pack_rows}", flush=True)
    if "mega" in what:
        mega = eng._build_mega_jit()
        act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)
        c = report(f"mega n={eng.block_size} sampled", mega, params, i32(B),
                   kv, tables, i32(B), act, i32(B), f32(B), f32(B), i32(B),
                   n_steps=eng.block_size, do_sample=True)
        if c is not None and "text" in what:
            out = os.path.join("chiprun_out", "aot_afmoe_mega.txt")
            os.makedirs("chiprun_out", exist_ok=True)
            open(out, "w").write(c.as_text())
            print("  HLO ->", out)
    if "chunk" in what:
        widths, g = [], 1
        while g < max(B, eng._pack_rows):
            g *= 2
        widths.append(g)
        g = 1
        while g < eng._pack_rows:
            g *= 2
        if g not in widths:
            widths.append(g)
        for g in widths:
            report(f"chunk g={g} x {C}", eng._chunk_fn(g), params, i32(g, C),
                   kv, rows(g), i32(g))
    if "first" in what:
        from paddle_tpu.core import autograd_engine
        from paddle_tpu.jit.api import _Swap

        def first(params, last, kv, rows, true_len):
            sub = {"kv": kv, "tables": rows}
            with autograd_engine.no_grad(), _Swap(eng._tensors, params):
                logits, sub = eng.model.paged_token_step(last, sub,
                                                         true_len - 1)
            return logits, sub["kv"]

        report(f"first-token step g={B}", jax.jit(first, donate_argnums=2),
               params, i32(B), kv, rows(B), i32(B))


if __name__ == "__main__":
    main()
