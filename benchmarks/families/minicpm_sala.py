"""MiniCPM-SALA (block-sparse softmax attention layers and Lightning
linear-attention layers named layer by layer, muP scaling): configuration file
-> the program's classes."""

from __future__ import annotations

import dataclasses

# the program's model module first: a checkout without it (the parent of PR 52)
# fails here, at once, before anything touches a device
from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import SALA_CONFIGS, SalaForCausalLM

from benchmarks.reference import minicpm_sala as reference  # noqa: F401  (the family's plain reference)

PUBLISHED_PRESET = "minicpm-sala"
# the selection's sizes as the file's ``assumed.sparse_config`` states them, by
# the reference's names; the rehearsal's are the tiny preset's, written out
SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk", "init_blocks", "window_size")
TINY = {
    "mixer_types": ["lightning-attn", "minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    "kernel_size": 4, "kernel_stride": 2, "block_size": 4, "topk": 6, "init_blocks": 1,
    "window_size": 6,
}
# what the reference needs beyond the program's config object and cannot take
# from it: ``model_config`` leaves the file's own list and sizes here
_FROM_FILE = dict(TINY)


def model_config(cfg: dict, rehearsal: bool, **overrides):
    """The program's ``SalaConfig`` with the file's sizes. The rehearsal takes
    the program's tiny preset instead."""
    _FROM_FILE.clear()
    if rehearsal:
        _FROM_FILE.update(TINY)
        return dataclasses.replace(SALA_CONFIGS[cfg["rehearsal"]["preset"]], **overrides)
    if cfg["attn_use_rope"] or not cfg["lightning_use_rope"] or not cfg["qk_norm"] \
            or cfg["lightning_scale"] != "1/sqrt(d)" or cfg["hidden_act"] != "silu" \
            or cfg["attention_bias"] or not (cfg["use_output_gate"] and cfg["use_output_norm"]
                                             and cfg["attn_use_output_gate"]) \
            or cfg["lightning_nkv"] != cfg["lightning_nh"] \
            or cfg["lightning_head_dim"] != cfg["head_dim"] \
            or len(cfg["mixer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(
            "no rotary in the sparse layers, rotary in the Lightning ones, per-head q/k "
            "norms, both output gates, Lightning's output norm and one kv head a query head "
            "there are the model; the file says otherwise")
    sparse = cfg["assumed"]["sparse_config"]
    _FROM_FILE.update({"mixer_types": list(cfg["mixer_types"]), **{k: sparse[k] for k in SPARSE_KEYS}})
    # sizes from the file; every other field (remat, precision, the state's
    # dtype) stays as the program's own preset ships it
    return dataclasses.replace(
        SALA_CONFIGS[PUBLISHED_PRESET],
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"], rope_theta=float(cfg["rope_theta"]),
        mixer_types=tuple(cfg["mixer_types"]), lightning_heads=cfg["lightning_nh"],
        scale_emb=float(cfg["scale_emb"]), scale_depth=float(cfg["scale_depth"]),
        mup_denominator=cfg["mup_denominator"], dim_model_base=cfg["dim_model_base"],
        kernel_size=sparse["kernel_size"], kernel_stride=sparse["kernel_stride"],
        sparse_block_size=sparse["block_size"], sparse_topk=sparse["topk"],
        sparse_init_blocks=sparse["init_blocks"], sparse_window=sparse["window_size"],
        **overrides,
    )


def reference_config(model_cfg) -> dict:
    """The reference's view (published key names) of what actually runs. The
    order of the mixers and the selection's sizes are the *file's* (or, for the
    rehearsal, the list written above), not the program's ``mixer_types`` /
    ``sparse_*`` fields: a wrong rule there must not be the reference's too.
    The per-head norms, the gates, the rotary's place and the decay are not
    here: the reference is this model and has no switch for any of them."""
    c = model_cfg
    return {
        "hidden_size": c.hidden_size,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.head_dim,
        "lightning_heads": c.lightning_heads,
        "rms_norm_eps": c.rms_norm_eps,
        "rope_theta": c.rope_theta,
        "scale_emb": c.scale_emb,
        "scale_depth": c.scale_depth,
        "mup_denominator": c.mup_denominator,
        "dim_model_base": c.dim_model_base,
        **_FROM_FILE,
    }


def train_model(model_cfg):
    return SalaForCausalLM(model_cfg)
