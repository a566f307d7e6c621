"""Scratch: how chipbench/tests/data/inflight.xplane.pb was recorded on the
chip (PR 38): the program's own engine at a toy size, so that
``metrics/_inflight.py`` and the readers built on it are tested on a real
chip trace with the real spans (``pt.serve.call`` with ``seq`` and
``drained``, ``pt.serve.wait`` with ``seq``, ``pt.serve.step`` with
``starved_us``). A one-layer llama of the tiny preset behind a 4-slot
prefix-cache engine serves a wave with an EOS id no token reaches (every
block is read back); the harness's own loop, ``step()`` then
``finished()``, with a 3 ms sleep between steps as the caller's poll. The
Python tracer is off: the file stays small.

    python3 chipbench/scratch/record_inflight_trace.py <out dir>
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out):
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from chipbench.metrics import _inflight, _program
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(11)
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    eng = ContinuousBatchingEngine(
        LlamaForCausalLM(cfg), max_batch=4, max_len=64, page_size=8,
        block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=16, extra_blocks=8))

    def wave(n=5):
        rng = np.random.default_rng(5)
        return [Request(rng.integers(3, cfg.vocab_size, 6 + 5 * i).astype(
            np.int32), max_new_tokens=5 + i, eos_token_id=1 << 20, seed=i + 1,
            **(dict(temperature=0.8, top_p=0.9) if i % 2 else {}))
            for i in range(n)]

    def serve(reqs):
        for r in reqs:
            eng.add_request(r)
        while eng.has_work():
            eng.step()
            eng.finished()
            time.sleep(0.003)

    for _ in range(2):                  # cold, then prefix-warm: all built
        serve(wave())
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind)
    stats0 = dict(eng.stats)
    d = os.path.join(out, "inflight_trace")
    shutil.rmtree(d, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    serve(wave())
    jax.profiler.stop_trace()
    stats1 = dict(eng.stats)
    p = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
    dst = os.path.join(out, "inflight.xplane.pb")
    shutil.copy(p, dst)
    shutil.rmtree(d, ignore_errors=True)
    print("bytes", os.path.getsize(dst))
    print("stats", {k: stats1[k] - stats0[k] for k in stats1
                    if isinstance(stats1[k], (int, float))
                    and stats1[k] != stats0[k]})
    prog = _program.read(dst)
    print(_program.describe(prog))
    print(_inflight.describe(prog))
    for c in _inflight.calls_of(prog) or ():
        print("call", c.seq, c.program, int(c.drained),
              f"{c.span.t0 * 1e3:.4f} {c.span.t1 * 1e3:.4f}")
    for name, t0, t1 in prog.modules:
        print("run ", name, f"{t0 * 1e3:.4f} {t1 * 1e3:.4f}")
    for s in prog.spans:
        if s.name in ("pt.serve.wait", "pt.serve.step"):
            print("span", s.name, f"{s.t0 * 1e3:.4f} {s.t1 * 1e3:.4f}", s.args)


if __name__ == "__main__":
    main(sys.argv[1])
