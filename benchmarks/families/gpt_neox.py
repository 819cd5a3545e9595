"""GPT-NeoX / Pythia: configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

from benchmarks.reference import gpt_neox as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "pythia-6.9b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    from neuronx_distributed_llama3_2_tpu.models.gptneox import GPTNEOX_CONFIGS

    if rehearsal:
        return dataclasses.replace(
            GPTNEOX_CONFIGS[cfg["rehearsal"]["preset"]], **overrides
        )
    # sizes from the file; remat, loss chunking, flash tiles and dtype stay
    # as the program's own preset ships them
    return dataclasses.replace(
        GPTNEOX_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rotary_emb_base"]), rms_norm_eps=cfg["layer_norm_eps"],
        rotary_pct=cfg["rotary_pct"],
        parallel_residual=cfg["use_parallel_residual"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    return {
        "num_attention_heads": model_cfg.num_heads,
        "head_dim": model_cfg.head_dim,
        "layer_norm_eps": model_cfg.rms_norm_eps,
        "rotary_pct": model_cfg.rotary_pct,
        "rotary_emb_base": model_cfg.rope_theta,
        "use_parallel_residual": model_cfg.parallel_residual,
    }


def train_model(model_cfg):
    from neuronx_distributed_llama3_2_tpu.models.gptneox import GPTNeoXForCausalLM

    return GPTNeoXForCausalLM(model_cfg)
