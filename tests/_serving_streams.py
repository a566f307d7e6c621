"""The streams of a ``models/llama`` engine at the chat-batch cell's rehearsal
size (hidden 64, 2 layers, heads 4/2 of 16, 512 words; fused, prefix cache,
pages of 4, blocks of 4), greedy and seeded-sampled requests mixed over
shared prefixes. ``tests/data/serving_llama_streams.json`` holds them: the
greedy streams (every fourth request) and the cache's counters as the parent
commit of PR 28 produced them (``python tests/_serving_streams.py <out>`` in
a checkout of it), the sampled streams as PR 39 produced them (``sample_rows``
draws its one uniform a row against the kept tokens in ID order there, in
sorted order from PR 29 on, so a key draws another token; the greedy streams
were checked equal to the older file each time it was re-recorded);
``test_serving_state.py`` holds every later tree to them byte for byte."""

import json
import os
import sys

import numpy as np


def llama_streams():
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(2028)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, initializer_range=0.1, dtype="float32"))
    eng = ContinuousBatchingEngine(
        model, max_batch=8, max_len=64, page_size=4, block_size=4,
        prefix_cache=PrefixCacheConfig(extra_blocks=8))
    rng = np.random.Generator(np.random.PCG64(28))
    prefixes = rng.integers(3, 512, (2, 8)).astype(np.int32)
    reqs = []
    for i in range(24):
        n = int(rng.integers(12, 40))
        tail = rng.integers(3, 512, n - 8).astype(np.int32)
        prompt = np.concatenate([prefixes[i % 2], tail])
        if i % 6 == 5:                      # page-aligned: a full-prompt hit
            prompt = reqs[-1].prompt[:16].copy()
        kw = {} if i % 4 == 0 else dict(temperature=0.7, top_p=0.95,
                                        seed=1000 + i)
        reqs.append(Request(prompt, max_new_tokens=int(rng.integers(4, 16)),
                            eos_token_id=2, **kw))
    for r in reqs[:16]:
        eng.add_request(r)
    eng.run_until_done()
    for r in reqs[16:]:
        eng.add_request(r)
    eng.run_until_done()
    return {"streams": [[int(t) for t in r.output] for r in reqs],
            "hit_tokens": int(eng.stats["hit_tokens"]),
            "cow_copies": int(eng.stats["cow_copies"])}


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.getcwd())
    with open(sys.argv[1], "w") as f:
        json.dump(llama_streams(), f)
