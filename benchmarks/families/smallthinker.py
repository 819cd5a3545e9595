"""SmallThinker (router before attention, ReLU-gated softmax top-k experts,
window rotary layers mixed with full layers that carry no position):
configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 57)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.smallthinker import (
    SMALLTHINKER_CONFIGS, SmallThinkerForCausalLM,
)

from benchmarks.reference import smallthinker as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "smallthinker-21b-a3b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``SmallThinkerConfig`` with the file's sizes and lists
    (which refuses lists that disagree in length or within a kind); the
    rehearsal takes the program's tiny preset instead."""
    if rehearsal:
        return dataclasses.replace(SMALLTHINKER_CONFIGS[cfg["rehearsal"]["preset"]], **overrides)
    assert cfg["moe_primary_router_apply_softmax"] is True and cfg["norm_topk_prob"] is True
    assert cfg["rope_scaling"] is None
    # sizes and architecture facts from the file; every other field (remat,
    # kernel and precision choices) stays as the program's own preset ships it
    return dataclasses.replace(
        SMALLTHINKER_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_ffn_hidden_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]),
        sliding_window=cfg["sliding_window_size"], rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. Where
    the router reads, the ReLU gate, the softmax, the renormalised gates and the
    window's convention are not here: the reference is this model and has no
    switch for any of them."""
    c = model_cfg
    return {
        "head_dim": c.head_dim,
        "num_key_value_heads": c.num_kv_heads,
        "rms_norm_eps": c.rms_norm_eps,
        "sliding_window_layout": list(c.sliding_window_layout),
        "rope_layout": list(c.rope_layout),
        "sliding_window_size": c.sliding_window,
        "rope_theta": c.rope_theta,
        "moe_num_active_primary_experts": c.top_k,
    }


def train_model(model_cfg):
    return SmallThinkerForCausalLM(model_cfg)
