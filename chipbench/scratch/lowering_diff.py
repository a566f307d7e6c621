#!/usr/bin/env python3
"""Do a served cell's programs lower alike from two checkouts? (scratch,
never a run; no chip: the lowering is made on the CPU for a described v5e.)

    python3 chipbench/scratch/lowering_diff.py <other checkout> <cell> ...

For each cell, from this checkout and from the other (``git archive
<commit> | tar -x -C <dir>``), each in a process of its own: the StableHLO of
the decode block (``block_size`` token steps, sampling) and of the widest
packed chunk, as ``jax.jit(...).trace(...).lower(lowering_platforms=
("tpu",))`` gives it. The Pallas kernels' serialized bodies
(``backend_config``) are cut out before comparing: they carry the source
file's path and line numbers; the kernels have their own tests. Prints a
digest a program a side and exits 1 where a pair differs, with the first
lines that do. What it shows: a PR that touched shared code (``dropless_ffn``,
the paged ops, the engine) left ANOTHER model's programs as they were; what it
cannot show: the time they take, which only a pair of runs on one chip does.
"""

import difflib
import hashlib
import os
import re
import subprocess
import sys

PAYLOAD = re.compile(r'backend_config = "(?:[^"\\]|\\.)*"')


def lower(root: str, name: str) -> dict:
    """{"decode_block": text, "chunk": text} from the checkout at ``root``
    (run in a process whose ``paddle_tpu`` and ``chipbench`` are that
    checkout's)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                      TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.harness import loader, serving

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the ops choose their TPU forms
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one)
    tree = lambda t: jax.tree_util.tree_map(sds, t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
    cell = loader.load(name, root=root)
    model = cell.adapter.build_model(
        cell.config, max_positions=int(cell.spec["engine"]["max_len"]))
    eng = serving.build_engine(cell, model)
    params, kv = tree(eng._params), tree(eng.caches["kv"])
    tables = sds(eng.caches["tables"])
    b, p, c = eng.max_batch, eng._maxp, eng._chunk_tokens
    live = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)
    block = eng._build_mega_jit().trace(
        params, i32(b), kv, tables, i32(b), live, i32(b), f32(b), f32(b),
        i32(b), n_steps=eng.block_size, do_sample=True)
    g = min(32, eng._pack_rows)
    extra = ([i32(g)] if eng._state_layers else []) + (
        [i32(g), i32(g)] if getattr(eng, "_seq_layers", []) else [])
    chunk = eng._chunk_fn(g).trace(params, i32(g, c), kv, i32(g, p), i32(g),
                                   *extra)
    return {k: PAYLOAD.sub("", t.lower(lowering_platforms=("tpu",)).as_text())
            for k, t in (("decode_block", block), ("chunk", chunk))}


def main(argv) -> int:
    if argv[:1] == ["--one"]:
        root, name, out = argv[1:4]
        for k, text in lower(root, name).items():
            with open(f"{out}.{k}.txt", "w") as f:
                f.write(text)
        return 0
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    other, cells = os.path.abspath(argv[0]), argv[1:]
    out_dir = os.path.join(here, ".scratch", "lowering")
    os.makedirs(out_dir, exist_ok=True)
    differ = 0
    for name in cells:
        texts = {}
        for side, root in (("this", here), ("other", other)):
            out = os.path.join(out_dir, f"{side}.{name}")
            # this file's lowering, on the checkout at ``root``
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--one", root, name, out], check=True,
                           stdout=subprocess.DEVNULL)
            for k in ("decode_block", "chunk"):
                with open(f"{out}.{k}.txt") as f:
                    texts[side, k] = f.read()
        for k in ("decode_block", "chunk"):
            a, b = texts["this", k], texts["other", k]
            print(f"{name} {k}: this "
                  f"{hashlib.sha256(a.encode()).hexdigest()[:16]} "
                  f"({len(a)} bytes) other "
                  f"{hashlib.sha256(b.encode()).hexdigest()[:16]} "
                  f"({len(b)} bytes): {'SAME' if a == b else 'DIFFER'}")
            if a != b:
                differ += 1
                lines = difflib.unified_diff(b.splitlines(), a.splitlines(),
                                             "other", "this", n=0, lineterm="")
                print("\n".join(line[:240] for _, line in zip(range(40),
                                                              lines)))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
