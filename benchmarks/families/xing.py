"""Xing4.0 (latent attention with a query latent under a four-stream,
Sinkhorn-mixed residual; sigmoid-bias router + a shared expert):
configuration file -> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 44)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.xing import XING_CONFIGS, XingForCausalLM

from benchmarks.reference import xing as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "xing4.0-29b-a4b"


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``XingConfig`` with the file's sizes; the rehearsal takes
    the program's tiny preset instead."""
    if rehearsal:
        return dataclasses.replace(XING_CONFIGS[cfg["rehearsal"]["preset"]], **overrides)
    yarn = cfg["rope_scaling"]
    # the facts the program has no switch for: it is this model
    assert yarn["type"] == "yarn" and {"mscale", "mscale_all_dim"} <= set(yarn), yarn
    assert cfg["scoring_func"] == "sigmoid" and cfg["topk_method"] == "noaux_tc", cfg
    assert cfg["n_group"] == cfg["topk_group"] == 1 and cfg["norm_topk_prob"] is True, cfg
    assert cfg["attention_bias"] is False and cfg["ep_size"] == 1 and cfg["moe_layer_freq"] == 1, cfg
    # sizes and architecture facts from the file; every other field (remat,
    # kernel and precision choices) stays as the program's own preset ships it
    return dataclasses.replace(
        XING_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"], first_k_dense=cfg["first_k_dense_held"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rms_norm_eps=cfg["rms_norm_eps"],
        yarn=(float(yarn["factor"]), int(yarn["original_max_position_embeddings"]),
              float(yarn["beta_fast"]), float(yarn["beta_slow"]),
              float(yarn["mscale"]), float(yarn["mscale_all_dim"])),
        num_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_res_clamp=(float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])),
        tie_word_embeddings=cfg["tie_word_embeddings"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. The
    residual's parameterisation, the sigmoid scores, the selection bias, the
    renormalised gates and the latents' norms are not here: the reference is
    this model and has no switch for any of them."""
    c = model_cfg
    factor, original, beta_fast, beta_slow, mscale, mscale_all_dim = c.yarn
    return {
        "num_attention_heads": c.num_heads,
        "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim,
        "rms_norm_eps": c.rms_norm_eps,
        "rope_theta": c.rope_theta,
        "rope_scaling": {
            "factor": factor, "original_max_position_embeddings": original,
            "beta_fast": beta_fast, "beta_slow": beta_slow,
            "mscale": mscale, "mscale_all_dim": mscale_all_dim,
        },
        "n_routed_experts": c.num_experts,
        "num_experts_per_tok": c.top_k,
        "routed_scaling_factor": c.routed_scaling_factor,
        "hc_mult": c.hc_mult,
        "hc_sinkhorn_iters": c.hc_sinkhorn_iters,
        "hc_eps": c.hc_eps,
        "mhc_h_res_clamp_min": c.hc_res_clamp[0],
        "mhc_h_res_clamp_max": c.hc_res_clamp[1],
    }


def train_model(model_cfg):
    return XingForCausalLM(model_cfg)
