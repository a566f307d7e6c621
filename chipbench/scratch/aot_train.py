"""Rehearsal 3 (scratch, never a run): AOT-compile the one-chip train step
for a described v5e at full widths, to fix depth and batch by what the
chip's compiler accepts. Usage:
  python chipbench/scratch/aot_train.py <config> <layers> <batch> <seq> <recompute 0|1>
Prints memory_analysis() or the compiler's refusal."""

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
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench.adapters import llama_block


def main():
    name, layers, batch, seq, remat = sys.argv[1:6]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = json.load(open(os.path.join(here, "configs", name + ".json")))
    cfg["num_hidden_layers"] = int(layers)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"   # dispatch guards pick the kernels
    from paddle_tpu.distributed.auto_parallel import Engine

    # a one-layer twin gives the leaf shapes without allocating the model
    model = llama_block.build_model(cfg, max_positions=int(seq),
                                    recompute=bool(int(remat)))
    eng = Engine(model, mesh=None, lr=3e-4, clip_norm=1.0)
    sds = lambda a, dt=None: jax.ShapeDtypeStruct(a.shape, dt or a.dtype,
                                                  sharding=one)
    params = [sds(a) for a in eng.params]
    m = [sds(a, jnp.float32) for a in eng.params]
    ids = jax.ShapeDtypeStruct((int(batch), int(seq)), jnp.int32,
                               sharding=one)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    t0 = time.time()
    try:
        lowered = eng._build_step().trace(params, m, m, step, ids, ids).lower(
            lowering_platforms=("tpu",))
        print("tpu_custom_call:", lowered.as_text().count("tpu_custom_call"))
        c = lowered.compile()
        ma = c.memory_analysis()
        print(f"OK layers={layers} batch={batch} seq={seq} remat={remat} "
              f"compile {time.time() - t0:.0f}s")
        print(ma)
        tot = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        print(f"arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, temps "
              f"{ma.temp_size_in_bytes / 1e9:.2f} GB, total live "
              f"{tot / 1e9:.2f} GB")
    except Exception as e:   # the compiler's refusal is the answer
        print(f"REFUSED layers={layers} batch={batch} seq={seq} "
              f"remat={remat} after {time.time() - t0:.0f}s: "
              f"{str(e)[:1500]}")


if __name__ == "__main__":
    main()
