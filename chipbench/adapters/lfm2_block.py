"""Adapter: an ``lfm2_moe`` configuration (HF keys) onto the program's
``paddle_tpu.models.lfm2`` block (gated short convolutions, GQA with QK-norm,
leading dense layers, routed experts behind a sigmoid router, tied head).

The model is built under ``LazyGuard``: its parameters hold shape and type
and no device array until ``assign`` hands them the seeded weights, so the
chip holds one copy of a model that fills two thirds of it.
"""

from __future__ import annotations

#: program parameter name (model.named_parameters) -> reference leaf
_TOP = {"model.embed_tokens_weight": "embed", "model.norm.weight": "final_norm"}
_LAYER = {"operator_norm.weight": "op_norm",
          "conv.in_proj_weight": "w_in", "conv.conv_weight": "conv_k",
          "conv.out_proj_weight": "w_out",
          "self_attn.q_proj_weight": "wq", "self_attn.k_proj_weight": "wk",
          "self_attn.v_proj_weight": "wv", "self_attn.o_proj_weight": "wo",
          "self_attn.q_norm.weight": "q_gain",
          "self_attn.k_norm.weight": "k_gain",
          "ffn_norm.weight": "ffn_norm",
          "feed_forward.gate_proj_weight": "w1",
          "feed_forward.up_proj_weight": "w3",
          "feed_forward.down_proj_weight": "w2",
          "feed_forward.gate.gate_weight": "router",
          "feed_forward.gate.expert_bias": "expert_bias",
          "feed_forward.experts.w_gate": "experts_w1",
          "feed_forward.experts.w_up": "experts_w3",
          "feed_forward.experts.w_down": "experts_w2"}


def leaf_of(name: str):
    """``(layer index or None, reference leaf name)`` of a program
    parameter."""
    if name in _TOP:
        return None, _TOP[name]
    parts = name.split(".")
    if parts[:2] != ["model", "layers"]:
        raise KeyError(f"chipbench: no reference leaf for parameter {name!r}")
    return int(parts[2]), _LAYER[".".join(parts[3:])]


def build_model(cfg: dict, *, max_positions: int, dtype: str = "bfloat16"):
    """The program's model for ``cfg``, without device arrays (``assign``
    brings them); ``max_positions`` is what the cell needs, not the
    published context."""
    import paddle_tpu
    from paddle_tpu.models.lfm2 import Lfm2Config, Lfm2ForCausalLM

    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("lfm2_block ties the head to the embedding")
    with paddle_tpu.LazyGuard():
        return Lfm2ForCausalLM(Lfm2Config(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            layer_types=cfg["layer_types"],
            num_dense_layers=cfg["num_dense_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            num_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            norm_topk_prob=cfg["norm_topk_prob"],
            use_expert_bias=cfg["use_expert_bias"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            conv_L_cache=cfg["conv_L_cache"], conv_bias=cfg["conv_bias"],
            norm_eps=float(cfg["norm_eps"]),
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            max_position_embeddings=max_positions,
            initializer_range=float(cfg.get("initializer_range", 0.02)),
            dtype=dtype))


def assign(model, weights: dict) -> None:
    """Put the benchmark's seeded weights into the program's parameters."""
    for name, p in model.named_parameters():
        layer, leaf = leaf_of(name)
        w = weights[leaf] if layer is None else weights["layers"][layer][leaf]
        if tuple(w.shape) != tuple(p._data.shape):
            raise ValueError(f"chipbench: {name} is {tuple(p._data.shape)}, "
                             f"the seeded leaf {leaf} is {tuple(w.shape)}")
        p._data = w.astype(p._data.dtype)
