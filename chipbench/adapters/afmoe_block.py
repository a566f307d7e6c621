"""Adapter: an ``afmoe`` configuration (HF keys) onto the program's
``paddle_tpu.models.afmoe`` block (window attention with rotary beside full
attention without, a sigmoid gate on the attention output, four norms a
layer, SwiGLU experts behind a sigmoid router beside a shared expert;
untied head).

``num_experts`` of the configuration as it is run is what this chip HOLDS,
experts ``[0, held)``; the router's width is the published count, which
``published`` states beside it. The model is built under ``LazyGuard`` and
``assign`` hands it the seeded weights: one copy on the chip.
"""

from __future__ import annotations

#: program parameter name (model.named_parameters) -> reference leaf
_TOP = {"model.embed_tokens_weight": "embed", "model.norm.weight":
        "final_norm", "lm_head_weight": "head"}
_LAYER = {"input_layernorm.weight": "attn_norm",
          "self_attn.q_proj_weight": "wq", "self_attn.k_proj_weight": "wk",
          "self_attn.v_proj_weight": "wv",
          "self_attn.gate_proj_weight": "wg",
          "self_attn.o_proj_weight": "wo",
          "self_attn.q_norm.weight": "q_gain",
          "self_attn.k_norm.weight": "k_gain",
          "post_attention_layernorm.weight": "post_attn_norm",
          "pre_mlp_layernorm.weight": "pre_mlp_norm",
          "post_mlp_layernorm.weight": "post_mlp_norm",
          "mlp.gate_proj_weight": "w_gate", "mlp.up_proj_weight": "w_up",
          "mlp.down_proj_weight": "w_down",
          "mlp.gate.gate_weight": "router",
          "mlp.gate.expert_bias": "expert_bias",
          "mlp.experts.w_gate": "experts_gate",
          "mlp.experts.w_up": "experts_up",
          "mlp.experts.w_down": "experts_down",
          "mlp.shared.gate_proj_weight": "shared_gate",
          "mlp.shared.up_proj_weight": "shared_up",
          "mlp.shared.down_proj_weight": "shared_down"}


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
    brings them)."""
    import paddle_tpu
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM

    if cfg.get("tie_word_embeddings", False):
        raise ValueError("afmoe_block has an untied head")
    if cfg.get("score_func", "sigmoid") != "sigmoid" or any(
            int(cfg.get(key, 1)) != 1 for key in ("n_group", "topk_group")):
        raise ValueError("afmoe_block routes by a sigmoid with no group "
                         "limit")
    routed = int(cfg.get("published", {}).get("num_experts",
                                              cfg["num_experts"]))
    with paddle_tpu.LazyGuard():
        return AfmoeForCausalLM(AfmoeConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            layer_types=cfg["layer_types"],
            num_dense_layers=cfg["num_dense_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
            num_experts=routed, experts_held=(0, int(cfg["num_experts"])),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            num_shared_experts=cfg["num_shared_experts"],
            route_norm=bool(cfg["route_norm"]),
            route_scale=float(cfg["route_scale"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            mup_enabled=bool(cfg.get("mup_enabled", False)),
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
