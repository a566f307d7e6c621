"""Adapter: a ``nemotron_h`` configuration (HF keys) onto the program's
``paddle_tpu.models.nemotron_h`` block (one mixer a layer: Mamba-2,
attention without rotary, or relu^2 experts behind a sigmoid router beside a
shared expert; untied head).

``n_routed_experts`` of the configuration as it is run is what this chip
HOLDS, experts ``[0, held)``; the router's width is the published count,
which ``published`` states beside it. The model is built under
``LazyGuard`` and ``assign`` hands it the seeded weights: one copy on the
chip. A Mamba-2 layer's seeded ``A_log`` and ``dt_bias`` ride the family's
initialisation, which the program's own ``NemotronHMamba2`` starts from, and
its conv taps are scaled (``reference/nemotron_h.py`` ``on_family_init``):
``assign`` puts them through it, as the reference does.
"""

from __future__ import annotations

#: program parameter name (model.named_parameters) -> reference leaf
_TOP = {"model.embed_tokens_weight": "embed", "model.norm_f.weight":
        "final_norm", "lm_head_weight": "head"}
_LAYER = {"norm.weight": "norm",
          "mixer.in_proj_weight": "w_in", "mixer.conv_weight": "conv_w",
          "mixer.conv_bias": "conv_b", "mixer.dt_bias": "dt_bias",
          "mixer.A_log": "A_log", "mixer.D": "D",
          "mixer.norm_weight": "ssm_norm", "mixer.out_proj_weight": "w_out",
          "mixer.q_proj_weight": "wq", "mixer.k_proj_weight": "wk",
          "mixer.v_proj_weight": "wv", "mixer.o_proj_weight": "wo",
          "mixer.gate.gate_weight": "router",
          "mixer.gate.expert_bias": "e_score_correction_bias",
          "mixer.experts.w_up": "experts_up",
          "mixer.experts.w_down": "experts_down",
          "mixer.shared.up_proj_weight": "shared_up",
          "mixer.shared.down_proj_weight": "shared_down"}


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
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    if cfg.get("tie_word_embeddings", False):
        raise ValueError("nemotron_h_block has an untied head")
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not name "
                         "num_hidden_layers layers")
    routed = int(cfg.get("published", {}).get("n_routed_experts",
                                              cfg["n_routed_experts"]))
    with paddle_tpu.LazyGuard():
        return NemotronHForCausalLM(NemotronHConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            hybrid_override_pattern=cfg["hybrid_override_pattern"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            mamba_num_heads=cfg["mamba_num_heads"],
            mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
            ssm_state_size=cfg["ssm_state_size"],
            conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
            n_routed_experts=routed,
            experts_held=(0, int(cfg["n_routed_experts"])),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            moe_shared_expert_intermediate_size=cfg[
                "moe_shared_expert_intermediate_size"],
            norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            time_step_min=float(cfg.get("time_step_min", 1e-3)),
            time_step_max=float(cfg.get("time_step_max", 0.1)),
            norm_eps=float(cfg["layer_norm_epsilon"]),
            max_position_embeddings=max_positions,
            initializer_range=float(cfg.get("initializer_range", 0.02)),
            dtype=dtype))


def assign(model, weights: dict) -> None:
    """Put the benchmark's seeded weights into the program's parameters; a
    Mamba-2 layer's through the reference's ``on_family_init``."""
    from chipbench.reference.nemotron_h import on_family_init

    c = model.config
    init = {"mamba_num_heads": c.mamba_num_heads,
            "time_step_min": c.time_step_min,
            "time_step_max": c.time_step_max,
            "initializer_range": c.initializer_range}
    f32 = ("A_log", "dt_bias")            # float32 in the program too
    layers = [on_family_init(init, {k: v.astype("float32") if k in f32 else v
                                    for k, v in w.items()})
              if "A_log" in w else w for w in weights["layers"]]
    for name, p in model.named_parameters():
        layer, leaf = leaf_of(name)
        w = weights[leaf] if layer is None else layers[layer][leaf]
        if tuple(w.shape) != tuple(p._data.shape):
            raise ValueError(f"chipbench: {name} is {tuple(p._data.shape)}, "
                             f"the seeded leaf {leaf} is {tuple(w.shape)}")
        p._data = w.astype(p._data.dtype)
