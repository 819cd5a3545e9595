"""Jamba (Mamba-1 state-space layers with an attention layer every period,
no positional term): configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 49)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.jamba import JAMBA_CONFIGS, JambaForCausalLM

from benchmarks.reference import jamba as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "jamba2-3b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``JambaConfig`` with the file's sizes. The rehearsal
    takes the program's tiny preset instead."""
    if rehearsal:
        return dataclasses.replace(JAMBA_CONFIGS[cfg["rehearsal"]["preset"]], **overrides)
    if cfg["num_experts"] != 1 or cfg["sliding_window"] is not None or cfg["mamba_proj_bias"] \
            or not cfg["mamba_conv_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("a dense feed-forward, full attention, a biased convolution and "
                         "unbiased projections are the model; the file says otherwise")
    # sizes from the file; every other field (remat, precision, the state's
    # dtype) stays as the program's own preset ships it
    return dataclasses.replace(
        JAMBA_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        rms_norm_eps=cfg["rms_norm_eps"], tie_word_embeddings=cfg["tie_word_embeddings"],
        attn_layer_period=cfg["attn_layer_period"], attn_layer_offset=cfg["attn_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_dt_rank=cfg["mamba_dt_rank"], mamba_expand=cfg["mamba_expand"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. The
    three inner norms, the missing rotary and the dense feed-forward are not
    here: the reference is this model and has no switch for any of them."""
    c = model_cfg
    return {
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim,
        "rms_norm_eps": c.rms_norm_eps,
        "mamba_d_state": c.mamba_d_state,
        "mamba_d_conv": c.mamba_d_conv,
        "mamba_dt_rank": c.mamba_dt_rank,
        # the family's published rule, written here on its own: a wrong rule
        # in the program's ``layer_kinds`` must not be the reference's too
        "layer_kinds": [
            "attention" if i % c.attn_layer_period == c.attn_layer_offset else "mamba"
            for i in range(c.num_layers)],
    }


def train_model(model_cfg):
    return JambaForCausalLM(model_cfg)
