"""Rehearsal 3 (scratch, never a run): AOT-compile the serving engine's
programs for a described v5e at the cell's sizes: the decode mega-step, the
widest packed prefill chunk and the widest first-token step. Prints compile
seconds and memory_analysis() of each, or the compiler's refusal.
  python chipbench/scratch/aot_serve.py <config> <max_batch> <max_len> [layers]"""

import json
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

from chipbench.adapters import llama_block


def main():
    name, max_batch, max_len = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = json.load(open(os.path.join(here, "configs", name + ".json")))
    if len(sys.argv) > 4:
        cfg["num_hidden_layers"] = int(sys.argv[4])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    model = llama_block.build_model(cfg, max_positions=max_len)
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, max_len=max_len, page_size=16,
        block_size=16, fused=True,
        prefix_cache=PrefixCacheConfig(extra_blocks=256))
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one)
    tree = lambda t: jax.tree_util.tree_map(sds, t)
    params = tree(eng._params)
    kv = tree(eng.caches["kv"])
    tables = sds(eng.caches["tables"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    B, P, C = max_batch, eng._maxp, eng._chunk_tokens

    def report(what, fn, *args, **kw):
        t0 = time.time()
        try:
            c = fn.trace(*args, **kw).lower(
                lowering_platforms=("tpu",)).compile()
            ma = c.memory_analysis()
            print(f"OK {what}: compile {time.time() - t0:.0f}s, arguments "
                  f"{ma.argument_size_in_bytes / 1e9:.2f} GB, temps "
                  f"{ma.temp_size_in_bytes / 1e9:.2f} GB, outputs-aliased "
                  f"{(ma.output_size_in_bytes - ma.alias_size_in_bytes) / 1e9:.2f} GB",
                  flush=True)
        except Exception as e:
            print(f"REFUSED {what} after {time.time() - t0:.0f}s: "
                  f"{str(e)[:1200]}", flush=True)

    mega = eng._build_mega_jit()
    act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)
    for n, s in ((16, True), (1, False)):
        report(f"mega n={n} sample={s}", mega, params, i32(B), kv, tables,
               i32(B), act, i32(B), f32(B), f32(B), i32(B), n_steps=n,
               do_sample=s)
    g = 1
    while g < max_batch:
        g *= 2
    report(f"chunk g={g} x {C}", eng._chunk_fn(g), params, i32(g, C), kv,
           i32(g, P), i32(g))
    report("chunk g=1", eng._chunk_fn(1), params, i32(1, C), kv, i32(1, P),
           i32(1))


if __name__ == "__main__":
    main()
