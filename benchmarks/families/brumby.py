"""Brumby (power retention): configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 36)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.brumby import (
    BRUMBY_CONFIGS, BrumbyForCausalLM,
)

from benchmarks.reference import brumby as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "brumby-14b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``BrumbyConfig`` with the file's sizes. The rehearsal
    takes the program's tiny preset instead."""
    if rehearsal:
        return dataclasses.replace(BRUMBY_CONFIGS[cfg["rehearsal"]["preset"]], **overrides)
    if cfg["rope_scaling"] is not None or cfg["use_sliding_window"] \
            or cfg["max_window_layers"] < cfg["num_hidden_layers"]:
        raise ValueError("every layer is a retention layer with plain rotary tables; the file says otherwise")
    # sizes from the file; every other field (remat, precision, the state's
    # dtype) stays as the program's own preset ships it
    return dataclasses.replace(
        BRUMBY_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. The
    degree, the gate, the normaliser and the per-head QK-norm are not here:
    the reference is this model and has no switch for any of them."""
    return {
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "rope_theta": model_cfg.rope_theta,
        "retention_eps": model_cfg.retention_eps,
    }


def train_model(model_cfg):
    return BrumbyForCausalLM(model_cfg)
