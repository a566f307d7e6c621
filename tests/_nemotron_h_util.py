"""Shared by the nemotron_h tests: a tiny configuration that holds all three
mixers (hidden 64; Mamba-2 of 8 heads x 8 over a state of 16 in 2 groups,
chunks of 8; GQA 4/2 of 16; 8 routed experts of 3 a token, 32 wide, and a
shared one of 48), seeded weights from the benchmark's maker, and the plain
reference."""

import numpy as np

from _lfm2_util import engine, serve  # noqa: F401  (the same tiny engine)

TINY = {
    "model_type": "nemotron_h", "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "vocab_size": 512,
    "tie_word_embeddings": False, "initializer_range": 0.1,
}


def reference():
    from chipbench.reference import nemotron_h

    return nemotron_h


def seeded_model(seed=5, dtype="float32", cfg=TINY, max_positions=128,
                 long_memory=False):
    """(model, top weights, layer weights function) on one seed: the
    program's model built under LazyGuard and assigned, and the reference's
    float32 leaves of the same values. ``long_memory`` sets ``A_log`` and
    ``dt_bias`` by hand on both sides, EVERY head alike (``A`` about -1, ``dt``
    about 0.005: a state keeps half of itself for some 140 positions); the
    seeded ones ride the family's initialisation, where only the first
    heads last that long."""
    import jax.numpy as jnp

    from chipbench.adapters import nemotron_h_block
    from chipbench.harness import weights as W

    table = reference().leaf_table(cfg)
    model = nemotron_h_block.build_model(cfg, max_positions=max_positions,
                                         dtype=dtype)
    whole = W.model_weights(table, seed, dtype=jnp.float32
                            if dtype == "float32" else jnp.bfloat16)

    a_log, dt_bias = reference().family_init(cfg)

    def by_hand(w):
        # leaves are ADDED to the init, and hold bfloat16's values
        if long_memory and "A_log" in w:
            leaf = lambda v, like: jnp.asarray(v, jnp.bfloat16).astype(
                like.dtype)
            w = dict(w, A_log=leaf(-a_log, w["A_log"]),
                     dt_bias=leaf(-5.25 - dt_bias, w["dt_bias"]))
        return w

    whole["layers"] = [by_hand(w) for w in whole["layers"]]
    nemotron_h_block.assign(model, whole)
    return (model, W.top_weights(table, seed),
            lambda i: by_hand(W.layer_weights(table, seed, i)))


def reference_logits(ids, top, layer, cfg=TINY):
    """Float32 logits [s, vocab] of ids [s] by the plain reference."""
    ref = reference()
    x = ref.hidden_states_many(cfg, [np.asarray(ids, np.int32)[None]],
                               layer, top)[0][0]
    return np.asarray(ref.logits_of(cfg, x, top))
