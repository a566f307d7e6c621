"""In-process A/B of the north-star llama config: plain CE vs fused chunked CE
vs fused CE + flash_mlp remat. Sequential in ONE process (July 2026 chip
throughput varied wildly across processes; see docs). Each leg frees the
previous model.
"""

import gc
import json
import time

import numpy as np


def run(tag, **over):
    import jax

    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16,
        num_key_value_heads=4, max_position_embeddings=4096,
        dtype="bfloat16", recompute=True, **over)
    batch, seq, iters = 4, 4096, 8
    model = LlamaForCausalLM(cfg)
    eng = Engine(model, mesh=None, lr=1e-4, clip_norm=1.0)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
               for _ in range(iters)]
    loss = eng.step(batches[0], batches[0]); jax.device_get(loss)
    loss = eng.step(batches[0], batches[0]); jax.device_get(loss)
    t0 = time.perf_counter()
    for ids in batches:
        loss = eng.step(ids, ids)
    jax.device_get(loss)
    dt = time.perf_counter() - t0
    tok = batch * seq * iters / dt
    n = cfg.num_params()
    fpt = 6.0 * n + 6.0 * cfg.num_hidden_layers * cfg.hidden_size * seq
    mfu = tok * fpt / 459e12
    print(json.dumps({"tag": tag, "tokens_per_sec": round(tok, 1),
                      "mfu": round(mfu, 4), "loss": round(float(loss), 3)}),
          flush=True)
    del eng, model
    gc.collect()
    return mfu


if __name__ == "__main__":
    import sys

    legs = sys.argv[1:] or ["plain", "fused", "fused_mlp"]
    for leg in legs:
        try:
            if leg == "plain":
                run("plain_ce", fused_ce=False)
            elif leg == "fused":
                run("fused_ce", fused_ce=True)
            elif leg == "fused_mlp":
                run("fused_ce+flash_mlp", fused_ce=True,
                    remat_policy="flash_mlp")
            elif leg == "fused_c512":
                run("fused_ce_chunk512", fused_ce=True, fused_ce_chunk=512)
            elif leg == "fused_c2048":
                run("fused_ce_chunk2048", fused_ce=True, fused_ce_chunk=2048)
        except Exception as e:
            print(json.dumps({"tag": leg, "error": repr(e)}), flush=True)
            gc.collect()
