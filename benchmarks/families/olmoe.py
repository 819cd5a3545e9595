"""OLMoE: configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

from benchmarks.reference import olmoe as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "olmoe-1b-7b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``OlmoeConfig`` with the file's sizes. The rehearsal
    takes the program's tiny preset instead."""
    from neuronx_distributed_llama3_2_tpu.models.olmoe import OLMOE_CONFIGS

    if rehearsal:
        return dataclasses.replace(
            OLMOE_CONFIGS[cfg["rehearsal"]["preset"]], **overrides
        )
    # sizes and the two architecture facts from the file; every other field
    # (capacity factor, remat, kernel and precision choices) stays as the
    # program's own preset ships it
    return dataclasses.replace(
        OLMOE_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_norm_eps=cfg["rms_norm_eps"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        normalize_top_k=cfg["norm_topk_prob"], clip_qkv=cfg["clip_qkv"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. The
    QK-norm and the unnormalised gates are not here: the reference is OLMoE
    and has no switch for either."""
    return {
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "rope_theta": model_cfg.rope_theta,
        "num_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.top_k,
    }


def train_model(model_cfg):
    from neuronx_distributed_llama3_2_tpu.models.olmoe import OlmoeForCausalLM

    return OlmoeForCausalLM(model_cfg)
