"""Scratch: how chipbench/tests/data/scoped.xplane.pb was recorded on the
chip (PR 24): a small stand-in for what the program names, so that
``metrics/_program.py`` and the readers built on it are tested on a real
chip trace. Two jitted programs under the program's own names:

- ``pt_decode_block``: a fixed part with no scope (a scaled copy), then a
  token loop (``lax.scan`` of 4: a ``while`` on the chip) holding a matmul
  under ``pt.attn``, a sort under ``pt.sampler`` and a tiny Pallas kernel
  with ``name="pt_tiny_kernel"`` under a scope of that name;
- ``pt_train_step``: value and gradient of a matmul under ``pt.mlp`` and a
  log-sum-exp loss under ``pt.fused_ce``.

Host side, twice: ``pt.serve.step`` holding ``pt.serve.decode.dispatch``
(the call), ``pt.serve.wait`` (block_until_ready) and ``pt.serve.emit`` (a
20 ms sleep: a device gap the host's own work explains); then a
``pt.train.step`` round the train program. The Python tracer is off: the
file stays small.

    python3 chipbench/scratch/record_scoped_trace.py <out dir>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def pt_decode_block(x):
    y = x * 0.5 + 1.0                       # the fixed part: no scope

    def body(c, _):
        with jax.named_scope("pt.attn"):
            c = jnp.tanh(c @ c)
        with jax.named_scope("pt.sampler"):
            c = jnp.sort(c, axis=-1)
        with jax.named_scope("pt_tiny_kernel"):
            c = pl.pallas_call(
                _double, name="pt_tiny_kernel",
                out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype))(c) * 0.5
        return c, None

    y, _ = jax.lax.scan(body, y, None, length=4)
    return y


def pt_train_step(w, x):
    def loss(w):
        with jax.named_scope("pt.mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("pt.fused_ce"):
            return jnp.mean(jax.scipy.special.logsumexp(h @ w, axis=-1))

    return jax.value_and_grad(loss)(w)


def main(out):
    decode, train = jax.jit(pt_decode_block), jax.jit(pt_train_step)
    x = jnp.full((1024, 1024), 0.01, jnp.float32)
    w = jnp.full((1024, 1024), 0.01, jnp.float32)
    jax.block_until_ready((decode(x), train(w, x)))
    d = os.path.join(out, "scoped_trace")
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    note = jax.profiler.TraceAnnotation
    for step in (1, 2):
        with note("pt.serve.step", step=step, occupied=4, queued=0):
            with note("pt.serve.decode.dispatch", n_steps=4, rows=4):
                y = decode(x)
            with note("pt.serve.wait", what="decode_block"):
                y.block_until_ready()
            with note("pt.serve.emit", tokens=16, finished=0):
                time.sleep(0.02)
    with jax.profiler.StepTraceAnnotation("pt.train.step", step_num=1):
        jax.block_until_ready(train(w, x))
    jax.profiler.stop_trace()
    p = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(p, os.path.join(out, "scoped.xplane.pb"))
    shutil.rmtree(d, ignore_errors=True)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from chipbench.metrics import _program

    prog = _program.read(os.path.join(out, "scoped.xplane.pb"))
    print(_program.describe(prog))
    print(_program.describe(prog, "jit_pt_decode_block"))
    print(_program.describe(prog, "jit_pt_train_step"))
    for o in _program.leaf_ops(prog.ops)[:40]:
        print(f"{(o.t1 - o.t0) * 1e6:9.1f}us {o.name[:70]!r} {o.stack!r}")


if __name__ == "__main__":
    main(sys.argv[1])
