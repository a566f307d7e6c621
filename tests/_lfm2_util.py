"""Shared by the lfm2 tests: the tiny configuration of ISSUE 28 (hidden 64,
heads 4/2 of 16, 8 experts of 4 a token, 5 layers ``[conv, full, conv, conv,
conv]`` with one dense), seeded weights from the benchmark's maker, and the
plain reference."""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {
    "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 64, "intermediate_size": 128,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "max_position_embeddings": 128, "moe_intermediate_size": 32,
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 4,
    "num_hidden_layers": 5, "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 512,
    "tie_word_embeddings": True, "initializer_range": 0.1,
}


def reference():
    from chipbench.reference import lfm2

    return lfm2


def seeded_model(seed=5, dtype="float32", cfg=TINY, max_positions=128):
    """(model, top weights, layer weights function) on one seed: the
    program's model built under LazyGuard and assigned, and the reference's
    float32 leaves of the same values."""
    import jax.numpy as jnp

    from chipbench.adapters import lfm2_block
    from chipbench.harness import weights as W

    table = reference().leaf_table(cfg)
    model = lfm2_block.build_model(cfg, max_positions=max_positions,
                                   dtype=dtype)
    lfm2_block.assign(model, W.model_weights(
        table, seed, dtype=jnp.float32 if dtype == "float32"
        else jnp.bfloat16))
    return (model, W.top_weights(table, seed),
            lambda i: W.layer_weights(table, seed, i))


def reference_logits(ids, top, layer, cfg=TINY):
    """Float32 logits [s, vocab] of ids [s] by the plain reference."""
    ref = reference()
    x = ref.hidden_states_many(cfg, [np.asarray(ids, np.int32)[None]],
                               layer, top)[0][0]
    return np.asarray(ref.logits_of(cfg, x, top))


def engine(model, **kw):
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    args = dict(max_batch=4, max_len=64, page_size=4, block_size=4,
                prefix_cache=PrefixCacheConfig(extra_blocks=8))
    args.update(kw)
    return ContinuousBatchingEngine(model, **args)


def serve(eng, reqs):
    for r in reqs:
        eng.add_request(r)
    eng.run_until_done()
    return [list(r.output) for r in reqs]
