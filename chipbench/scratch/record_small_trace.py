"""Scratch: how chipbench/tests/data/small.xplane.pb was recorded on the
chip (PR 23): three 2048^2 bf16 matmuls, a 20 ms host sleep, three more,
under bench.* annotations.   python3 chipbench/scratch/record_small_trace.py <out dir>"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1]
f = jax.jit(lambda x: (x @ x) * 0.5)
x = jnp.ones((2048, 2048), jnp.bfloat16)
f(x).block_until_ready()
d = os.path.join(out, "small_trace")
shutil.rmtree(d, ignore_errors=True)
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation("bench.burst_a"):
    for _ in range(3):
        x = f(x)
    x.block_until_ready()
with jax.profiler.TraceAnnotation("bench.sleep"):
    time.sleep(0.02)
with jax.profiler.TraceAnnotation("bench.burst_b"):
    for _ in range(3):
        x = f(x)
    x.block_until_ready()
jax.profiler.stop_trace()
p = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
shutil.copy(p, os.path.join(out, "small.xplane.pb"))
shutil.rmtree(d, ignore_errors=True)
