"""Shared by the afmoe tests: a tiny configuration with both attention kinds
(hidden 64; GQA 4/2 of 16; window 16; layers ``[sliding, sliding, sliding,
full, sliding]`` with one dense; 8 routed experts of 3 a token, 32 wide,
beside a shared one), seeded weights from the benchmark's maker, and the
plain reference."""

import numpy as np

from _lfm2_util import serve  # noqa: F401  (puts the repo's root on the path)

TINY = {
    "model_type": "afmoe", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention",
                    "sliding_attention"],
    "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "num_experts": 8, "num_experts_per_tok": 3, "num_shared_experts": 1,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "mup_enabled": True, "vocab_size": 512,
    "tie_word_embeddings": False, "initializer_range": 0.1,
}


def reference():
    from chipbench.reference import afmoe

    return afmoe


def seeded_model(seed=5, dtype="float32", cfg=TINY, max_positions=256):
    """(model, top weights, layer weights function) on one seed: the
    program's model built under LazyGuard and assigned, and the reference's
    float32 leaves of the same values."""
    import jax.numpy as jnp

    from chipbench.adapters import afmoe_block
    from chipbench.harness import weights as W

    table = reference().leaf_table(cfg)
    model = afmoe_block.build_model(cfg, max_positions=max_positions,
                                    dtype=dtype)
    afmoe_block.assign(model, W.model_weights(
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
    """Four slots of 128 on pages of 4, chunks of 8 (a slot takes one row a packed call):
    the window group's pool is 4 x (ceil((16 + 8) / 4) + 1) = 28 pages and
    its share of the extra ones, where ``max_len`` asks 32 pages a slot of
    the full group."""
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    args = dict(max_batch=4, max_len=128, page_size=4, block_size=4,
                prefix_cache=PrefixCacheConfig(prefill_chunk=8,
                                               extra_blocks=16))
    args.update(kw)
    return ContinuousBatchingEngine(model, **args)
