"""Sarvam (MLA + sigmoid-bias MoE): configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 33)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.sarvam import (
    SARVAM_CONFIGS, SarvamForCausalLM,
)

from benchmarks.reference import sarvam as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "sarvam-105b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``SarvamConfig`` with the file's sizes. The rehearsal
    takes the program's tiny preset instead, holding the share of its experts
    that the file's ``rehearsal`` block names."""
    if rehearsal:
        tiny = cfg["rehearsal"]
        return dataclasses.replace(
            SARVAM_CONFIGS[tiny["preset"]], experts_held=tiny["experts_held"],
            first_held_expert=tiny["first_held_expert"], **overrides,
        )
    # sizes and architecture facts from the file; every other field (remat,
    # kernel and precision choices) stays as the program's own preset ships it
    yarn = cfg["rope_scaling"]
    assert yarn["type"] == "deepseek_yarn", yarn
    return dataclasses.replace(
        SARVAM_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"], first_k_dense=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_attention_heads"],
        head_dim=cfg["q_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_norm_eps=cfg["rms_norm_eps"],
        yarn=(float(yarn["factor"]), int(yarn["original_max_position_embeddings"]),
              float(yarn["beta_fast"]), float(yarn["beta_slow"]),
              float(yarn["mscale"]), float(yarn["mscale_all_dim"])),
        num_experts=cfg["router_num_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"], first_held_expert=cfg["first_held_expert"],
        num_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs, and
    the share of the experts held. The sigmoid scores, the selection bias, the
    renormalised gates and the latent's norm are not here: the reference is
    this model and has no switch for any of them."""
    factor, original, beta_fast, beta_slow, mscale, mscale_all_dim = model_cfg.yarn
    return {
        "num_attention_heads": model_cfg.num_heads,
        "kv_lora_rank": model_cfg.kv_lora_rank,
        "qk_nope_head_dim": model_cfg.qk_nope_head_dim,
        "qk_rope_head_dim": model_cfg.qk_rope_head_dim,
        "v_head_dim": model_cfg.v_head_dim,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "rope_theta": model_cfg.rope_theta,
        "rope_scaling": {
            "factor": factor, "original_max_position_embeddings": original,
            "beta_fast": beta_fast, "beta_slow": beta_slow,
            "mscale": mscale, "mscale_all_dim": mscale_all_dim,
        },
        "num_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.top_k,
        "routed_scaling_factor": model_cfg.routed_scaling_factor,
        "experts_held": model_cfg.moe_config().held,
        "first_held_expert": model_cfg.first_held_expert,
    }


def train_model(model_cfg):
    return SarvamForCausalLM(model_cfg)
