"""Scratch (never a run): AOT-compile a cell's main program for a described
v5e and print what a trace would name: the module, ``memory_analysis()``,
the count of fusions and custom calls, device instructions by the ``pt``
name of their ``op_name``, and the Pallas instructions. On two commits the
first four lines must agree: names are metadata only.

  python chipbench/scratch/aot_names.py serve <config> <max_batch> <max_len> <block> <n_steps>
  python chipbench/scratch/aot_names.py train <config> <layers> <batch> <seq>
Imports the checkout it lies in. For another commit, unpack that commit
(``git archive``) into a directory ``.gitignore`` lists, copy this file to
the same place there and run that copy."""

import collections
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench.adapters import llama_block

PT = re.compile(r"(?<!jit\()pt[._][A-Za-z0-9_.]*[A-Za-z0-9_]")
INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? (fusion|custom-call|"
                   r"while|sort|copy|copy-start|convolution)\(")


def report(what, compiled, t0):
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    print(f"{what}: {text.splitlines()[0].split(',')[0]} "
          f"(compile {time.time() - t0:.0f}s)")
    print(f"memory: arguments {ma.argument_size_in_bytes} temps "
          f"{ma.temp_size_in_bytes} outputs {ma.output_size_in_bytes} "
          f"aliased {ma.alias_size_in_bytes} code "
          f"{ma.generated_code_size_in_bytes}")
    kinds, scopes, kernels = (collections.Counter(), collections.Counter(),
                              collections.Counter())
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        kinds[m.group(2)] += 1
        op = re.search(r'op_name="([^"]*)"', line)
        found = PT.findall(op.group(1)) if op else []
        scopes[found[-1] if found else "(no pt name)"] += 1
        if "tpu_custom_call" in line:
            kernels[re.sub(r"[.\d]+$", "", m.group(1))] += 1
    print("instructions:", dict(sorted(kinds.items())))
    print("pallas instructions by name:", dict(kernels))
    print("instructions by innermost pt name:", dict(scopes.most_common()))


def serve(name, max_batch, max_len, block, n_steps):
    cfg = json.load(open(os.path.join(HERE, "configs", name + ".json")))
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    model = llama_block.build_model(cfg, max_positions=max_len)
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, max_len=max_len, page_size=16,
        block_size=block, fused=True,
        prefix_cache=PrefixCacheConfig(extra_blocks=256))
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=ONE)
    tree = lambda t: jax.tree_util.tree_map(sds, t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=ONE)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=ONE)
    B = max_batch
    act = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=ONE)
    t0 = time.time()
    c = eng._build_mega_jit().trace(
        tree(eng._params), i32(B), tree(eng.caches["kv"]),
        sds(eng.caches["tables"]), i32(B), act, i32(B), f32(B), f32(B),
        i32(B), n_steps=n_steps, do_sample=True).lower(
        lowering_platforms=("tpu",)).compile()
    report(f"decode block n={n_steps} sampled", c, t0)


def train(name, layers, batch, seq):
    cfg = json.load(open(os.path.join(HERE, "configs", name + ".json")))
    cfg["num_hidden_layers"] = layers
    from paddle_tpu.distributed.auto_parallel import Engine

    model = llama_block.build_model(cfg, max_positions=seq, recompute=False)
    eng = Engine(model, mesh=None, lr=3e-4, clip_norm=1.0)
    sds = lambda a, dt=None: jax.ShapeDtypeStruct(a.shape, dt or a.dtype,
                                                  sharding=ONE)
    params = [sds(a) for a in eng.params]
    m = [sds(a, jnp.float32) for a in eng.params]
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=ONE)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=ONE)
    t0 = time.time()
    c = eng._build_step().trace(params, m, m, step, ids, ids).lower(
        lowering_platforms=("tpu",)).compile()
    report("train step", c, t0)


if __name__ == "__main__":
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    ONE = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"   # dispatch guards pick the kernels
    kind, name, *nums = sys.argv[1:]
    {"serve": serve, "train": train}[kind](name, *map(int, nums))
