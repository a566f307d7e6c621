"""Adapter: a decoder configuration (HF keys) onto the program's
``paddle_tpu.models.llama`` block (GQA, RMSNorm, rotary, SwiGLU, untied head).

The one place where chipbench names the program's model classes. A later
configuration of another family brings an adapter of its own, named in its
configuration file.
"""

from __future__ import annotations

#: program parameter name (model.named_parameters) -> reference leaf
_TOP = {"lm_head_weight": "head", "model.embed_tokens_weight": "embed",
        "model.norm.weight": "final_norm"}
_LAYER = {"input_layernorm.weight": "attn_norm",
          "self_attn.q_proj_weight": "wq", "self_attn.k_proj_weight": "wk",
          "self_attn.v_proj_weight": "wv", "self_attn.o_proj_weight": "wo",
          "post_attention_layernorm.weight": "mlp_norm",
          "mlp.gate_proj_weight": "w_gate", "mlp.up_proj_weight": "w_up",
          "mlp.down_proj_weight": "w_down"}


def leaf_of(name: str):
    """``(layer index or None, reference leaf name)`` of a program
    parameter."""
    if name in _TOP:
        return None, _TOP[name]
    parts = name.split(".")
    if parts[:2] != ["model", "layers"]:
        raise KeyError(f"chipbench: no reference leaf for parameter {name!r}")
    return int(parts[2]), _LAYER[".".join(parts[3:])]


def build_model(cfg: dict, *, max_positions: int, recompute: bool = False,
                dtype: str = "bfloat16"):
    """The program's model for ``cfg``. Its own initial weights are thrown
    away by ``assign``; ``max_positions`` is what the cell needs, not the
    published context."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    if hd * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("llama_block derives head_dim from hidden_size / "
                         "heads; this configuration needs another adapter")
    if cfg.get("sliding_window") or cfg.get("tie_word_embeddings"):
        raise ValueError("llama_block has no window and no tied head")
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        initializer_range=float(cfg.get("initializer_range", 0.02)),
        tie_word_embeddings=False, dtype=dtype, recompute=recompute))


def assign(model, weights: dict) -> None:
    """Put the benchmark's seeded weights into the program's parameters."""
    for name, p in model.named_parameters():
        layer, leaf = leaf_of(name)
        w = weights[leaf] if layer is None else weights["layers"][layer][leaf]
        if tuple(w.shape) != tuple(p._data.shape):
            raise ValueError(f"chipbench: {name} is {tuple(p._data.shape)}, "
                             f"the seeded leaf {leaf} is {tuple(w.shape)}")
        p._data = w.astype(p._data.dtype)
