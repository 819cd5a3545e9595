"""Laguna (window and full attention layers mixed, two head counts, two
rotary tables, per-head output gates, softmax top-k experts + a shared
expert): configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 42)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.laguna import LAGUNA_CONFIGS, LagunaForCausalLM

from benchmarks.reference import laguna as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "laguna-xs.2"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``LagunaConfig`` with the file's sizes and lists (which
    refuses lists that disagree in length and kinds it does not know); the
    rehearsal takes the program's tiny preset instead."""
    if rehearsal:
        return dataclasses.replace(LAGUNA_CONFIGS[cfg["rehearsal"]["preset"]], **overrides)
    rope = cfg["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    assert full["rope_type"] == "yarn" and window["rope_type"] == "default", rope
    assert window["partial_rotary_factor"] == 1 and cfg["gating"] is True
    assert cfg["moe_apply_router_weight_on_input"] is False and not cfg["attention_bias"]
    # sizes and architecture facts from the file; every other field (remat,
    # kernel and precision choices) stays as the program's own preset ships it
    return dataclasses.replace(
        LAGUNA_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        layer_types=tuple(cfg["layer_types"]),
        num_heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        yarn=(float(full["factor"]), int(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"]), float(full["attention_factor"])),
        partial_rotary_factor=float(full["partial_rotary_factor"]),
        window_rope_theta=float(window["rope_theta"]),
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. The
    gate's form, the softmax router, the renormalised gates, the ungated shared
    expert and the window's convention are not here: the reference is this
    model and has no switch for any of them."""
    c = model_cfg
    factor, original, beta_fast, beta_slow, attention_factor = c.yarn
    return {
        "head_dim": c.head_dim,
        "num_key_value_heads": c.num_kv_heads,
        "rms_norm_eps": c.rms_norm_eps,
        "layer_types": list(c.layer_types),
        "mlp_layer_types": list(c.mlp_layer_types),
        "sliding_window": c.sliding_window,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": c.rope_theta, "factor": factor,
                "original_max_position_embeddings": original, "beta_fast": beta_fast,
                "beta_slow": beta_slow, "attention_factor": attention_factor,
                "partial_rotary_factor": c.partial_rotary_factor,
            },
            "sliding_attention": {
                "rope_type": "default", "rope_theta": c.window_rope_theta,
                "partial_rotary_factor": 1,
            },
        },
        "num_experts": c.num_experts,
        "num_experts_per_tok": c.top_k,
        "moe_routed_scaling_factor": c.routed_scaling_factor,
    }


def train_model(model_cfg):
    return LagunaForCausalLM(model_cfg)
