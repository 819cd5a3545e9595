"""Checkpoint converter CLI: HF ↔ native, both directions, offline.

TPU-native replacement for the reference's converter tooling:
``scripts/checkpoint_converter.py:238`` (``merge_tp_checkpoints``: per-rank
TP shards → full HF state dict), ``:393`` (``convert_full_state_to_tp``:
full → per-rank shards) and ``optimizer/convert_zero_checkpoints.py:176``
(merge/split dp-sharded ZeRO optimizer states).

Under GSPMD most of that machinery dissolves: native checkpoints hold
*global* arrays (checkpoint/checkpoint.py), so there are no per-rank shards
to merge/split — resharding happens online at load via specs (elastic
resume, tested in test_checkpoint.py). What remains meaningful offline, and
what this CLI does:

- ``hf-to-native``: read an HF Llama checkpoint directory (safetensors or
  pytorch .bin) → write a native checkpoint tag loadable by
  ``load_checkpoint`` at any tp/pp/dp.
- ``native-to-hf``: read a native tag → write HF-format safetensors +
  config.json, loadable by ``transformers``.
- ``strip-optimizer``: rewrite a training checkpoint keeping only model
  weights (the role of the reference's optimizer-state merge for export:
  once merged the optimizer state is dropped for serving).

Usage::

    python -m neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter \
        --direction hf-to-native --model llama3.2-1b \
        --input /path/hf_dir --output /path/ckpt_dir --tag from_hf
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict

from neuronx_distributed_llama3_2_tpu.utils.logger import get_logger

logger = get_logger()


def load_hf_state_dict(path: str) -> Dict[str, Any]:
    """Read every *.safetensors (preferred) or pytorch_model*.bin in ``path``
    into one numpy state dict."""
    import numpy as np

    sd: Dict[str, Any] = {}
    st_files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if st_files:
        from safetensors.numpy import load_file

        for f in st_files:
            sd.update(load_file(os.path.join(path, f)))
        return sd
    bin_files = sorted(
        f
        for f in os.listdir(path)
        if f.startswith("pytorch_model") and f.endswith(".bin")
    )
    if not bin_files:
        raise FileNotFoundError(
            f"no *.safetensors or pytorch_model*.bin under {path}"
        )
    import torch

    for f in bin_files:
        t = torch.load(os.path.join(path, f), map_location="cpu", weights_only=True)
        sd.update({k: v.float().numpy() for k, v in t.items()})
    return sd


#: per-file shard budget for exported safetensors (HF convention)
_SHARD_BYTES = 5 * 2 ** 30


def save_hf_state_dict(sd: Dict[str, Any], path: str, config) -> None:
    """Write a safetensors HF checkpoint + minimal config.json.

    Tensors are cast to the model's compute dtype (bf16, matching published
    Llama-3 checkpoints — fp32 would double size and host memory) and split
    into ~5GB shards with a ``model.safetensors.index.json`` per the HF
    convention, so a 70B export neither OOMs the host in one buffer nor
    produces a single 140GB file."""
    import jax.numpy as jnp
    import numpy as np

    # MllamaConfig nests its dtype under text/vision; every other family
    # carries a top-level dtype
    cfg_dtype = getattr(config, "dtype", None)
    if cfg_dtype is None:
        cfg_dtype = config.text.dtype
    dtype = np.dtype(cfg_dtype) if cfg_dtype != jnp.bfloat16 else jnp.bfloat16
    itemsize = np.dtype(dtype).itemsize if dtype != jnp.bfloat16 else 2
    _write_sharded_safetensors(
        sd,
        path,
        base="model",
        itemsize=itemsize,
        cast=lambda v: np.ascontiguousarray(np.asarray(v).astype(dtype)),
    )
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(_hf_config_dict(config), f, indent=2)


def _write_sharded_safetensors(
    sd: Dict[str, Any], path: str, base: str, itemsize: int, cast
) -> None:
    """Greedy ~5GB shard split + ``{base}.safetensors[.index.json]`` naming
    (HF convention). Tensors are cast per shard at write time so the extra
    host footprint is one shard, not a full second copy of the model. Shared
    by the weight export (dtype-cast) and the optimizer export (raw fp32)."""
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    shards, cur, cur_bytes = [], [], 0
    for k, v in sd.items():
        nbytes = v.size * itemsize
        if cur and cur_bytes + nbytes > _SHARD_BYTES:
            shards.append(cur)
            cur, cur_bytes = [], 0
        cur.append(k)
        cur_bytes += nbytes
    shards.append(cur)

    def cast_shard(keys):
        return {k: cast(sd[k]) for k in keys}

    if len(shards) == 1:
        save_file(
            cast_shard(shards[0]), os.path.join(path, f"{base}.safetensors")
        )
        return
    total = sum(v.size * itemsize for v in sd.values())
    index = {"metadata": {"total_size": total}, "weight_map": {}}
    for i, keys in enumerate(shards):
        name = f"{base}-{i + 1:05d}-of-{len(shards):05d}.safetensors"
        save_file(cast_shard(keys), os.path.join(path, name))
        for k in keys:
            index["weight_map"][k] = name
    with open(os.path.join(path, f"{base}.safetensors.index.json"), "w") as f:
        json.dump(index, f, indent=2)


def _hf_config_dict(config) -> Dict[str, Any]:
    """Family-aware HF ``config.json`` contents, keyed off the config class
    (the converter serves every registry family, not just Llama)."""
    import jax.numpy as jnp

    name = type(config).__name__
    if name == "MllamaConfig":
        v, t = config.vision, config.text
        text_cfg = {
            "vocab_size": t.vocab_size,
            "hidden_size": t.hidden_size,
            "intermediate_size": t.intermediate_size,
            "num_hidden_layers": t.num_hidden_layers,
            "num_attention_heads": t.num_heads,
            "num_key_value_heads": t.num_kv_heads,
            "cross_attention_layers": list(t.cross_attention_layers),
            "rope_theta": t.rope_theta,
            "rms_norm_eps": t.rms_norm_eps,
            "max_position_embeddings": t.max_seq_len,
        }
        if t.rope_scaling is not None:
            factor, low, high, orig = t.rope_scaling
            text_cfg["rope_scaling"] = {
                "rope_type": "llama3",
                "factor": factor,
                "low_freq_factor": low,
                "high_freq_factor": high,
                "original_max_position_embeddings": orig,
            }
        return {
            "architectures": ["MllamaForConditionalGeneration"],
            "model_type": "mllama",
            "text_config": text_cfg,
            "vision_config": {
                "hidden_size": v.hidden_size,
                "intermediate_size": v.intermediate_size,
                "num_hidden_layers": v.num_hidden_layers,
                "num_global_layers": v.num_global_layers,
                "attention_heads": v.attention_heads,
                "image_size": v.image_size,
                "patch_size": v.patch_size,
                "num_channels": v.num_channels,
                "max_num_tiles": v.max_num_tiles,
                # transformers derives max_aspect_ratio_id from this list
                # (a read-only property there — emitting the id directly
                # crashes PretrainedConfig setattr); HF enumeration order:
                # width-major over width*height <= max_num_tiles
                "supported_aspect_ratios": [
                    [w, h]
                    for w in range(1, v.max_num_tiles + 1)
                    for h in range(1, v.max_num_tiles + 1)
                    if w * h <= v.max_num_tiles
                ],
                # derived on our side (hidden * (1 + collected layers)) but
                # an independent field in HF — omitting it would build the
                # projector at the 11B default 7680 for every other size
                "vision_output_dim": v.output_dim,
                "intermediate_layers_indices": list(
                    v.intermediate_layers_indices
                ),
                "norm_eps": v.norm_eps,
            },
            "torch_dtype": str(jnp.dtype(t.dtype)),
        }
    if name == "BertConfig":
        return {
            "architectures": ["BertForPreTraining"],
            "model_type": "bert",
            "hidden_size": config.hidden_size,
            "intermediate_size": config.intermediate_size,
            "num_hidden_layers": config.num_layers,
            "num_attention_heads": config.num_heads,
            "vocab_size": config.vocab_size,
            "max_position_embeddings": config.max_position_embeddings,
            "type_vocab_size": config.type_vocab_size,
            "layer_norm_eps": config.layer_norm_eps,
            "torch_dtype": str(jnp.dtype(config.dtype)),
        }
    if name == "GPTNeoXConfig" and config.rotary_interleaved:
        # transformers CodeGenConfig attribute names (n_embd/n_layer/...)
        return {
            "architectures": ["CodeGenForCausalLM"],
            "model_type": "codegen",
            "n_embd": config.hidden_size,
            "n_inner": config.intermediate_size,
            "n_layer": config.num_layers,
            "n_head": config.num_heads,
            "n_positions": config.max_seq_len,
            "n_ctx": config.max_seq_len,
            "rotary_dim": int(config.head_dim * config.rotary_pct),
            "vocab_size": config.vocab_size,
            "tie_word_embeddings": config.tie_word_embeddings,
            "torch_dtype": str(jnp.dtype(config.dtype)),
        }
    if name == "DbrxConfig":
        # transformers DbrxConfig attribute names (d_model/n_heads/...)
        return {
            "architectures": ["DbrxForCausalLM"],
            "model_type": "dbrx",
            "d_model": config.hidden_size,
            "n_heads": config.num_heads,
            "n_layers": config.num_layers,
            "max_seq_len": config.max_seq_len,
            "vocab_size": config.vocab_size,
            "tie_word_embeddings": config.tie_word_embeddings,
            "attn_config": {
                "clip_qkv": config.clip_qkv,
                "kv_n_heads": config.num_kv_heads,
                "rope_theta": config.rope_theta,
            },
            "ffn_config": {
                "ffn_hidden_size": config.intermediate_size,
                "moe_num_experts": config.num_experts,
                "moe_top_k": config.top_k,
            },
            "torch_dtype": str(jnp.dtype(config.dtype)),
        }
    cfg = {
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "vocab_size": config.vocab_size,
        "tie_word_embeddings": config.tie_word_embeddings,
        "max_position_embeddings": config.max_seq_len,
        "torch_dtype": str(jnp.dtype(config.dtype)),
    }
    if name == "GPTNeoXConfig":
        cfg.update(
            architectures=["GPTNeoXForCausalLM"],
            model_type="gpt_neox",
            rotary_pct=config.rotary_pct,
            rotary_emb_base=config.rope_theta,
            use_parallel_residual=config.parallel_residual,
            layer_norm_eps=config.rms_norm_eps,
        )
        return cfg
    cfg.update(
        num_key_value_heads=config.num_kv_heads,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=config.rope_theta,
    )
    if name == "OlmoeConfig":
        cfg.update(
            architectures=["OlmoeForCausalLM"],
            model_type="olmoe",
            num_experts=config.num_experts,
            num_experts_per_tok=config.top_k,
            norm_topk_prob=config.normalize_top_k,
            router_aux_loss_coef=config.router_aux_loss_coef,
        )
        return cfg
    if name == "MixtralConfig":
        cfg.update(
            architectures=["MixtralForCausalLM"],
            model_type="mixtral",
            num_local_experts=config.num_experts,
            num_experts_per_tok=config.top_k,
            router_aux_loss_coef=config.router_aux_loss_coef,
        )
        return cfg
    cfg.update(architectures=["LlamaForCausalLM"], model_type="llama")
    if config.rope_scaling is not None:
        # HF "llama3" rope scaling dict — omitting it would silently load
        # published Llama-3.2 weights with unscaled RoPE (review finding)
        factor, low, high, orig = config.rope_scaling
        cfg["rope_scaling"] = {
            "rope_type": "llama3",
            "factor": factor,
            "low_freq_factor": low,
            "high_freq_factor": high,
            "original_max_position_embeddings": orig,
        }
    return cfg


def _resolve_model(name: str) -> Dict[str, Any]:
    """Thin alias kept for CLI-internal use; the registry's public home is
    :func:`neuronx_distributed_llama3_2_tpu.models.resolve_model`."""
    from neuronx_distributed_llama3_2_tpu.models import resolve_model

    return resolve_model(name)


def hf_to_native(args) -> None:
    from neuronx_distributed_llama3_2_tpu.checkpoint import save_checkpoint

    entry = _resolve_model(args.model)
    if entry["from_hf"] is None:
        raise NotImplementedError(
            f"{args.model!r} has no from_hf converter in the model registry"
        )
    sd = load_hf_state_dict(args.input)
    params = entry["from_hf"](sd, entry["config"])
    save_checkpoint(args.output, tag=args.tag, model=params)
    logger.info("wrote native checkpoint %s/%s", args.output, args.tag)


def native_to_hf(args) -> None:
    import jax

    from neuronx_distributed_llama3_2_tpu.checkpoint import load_checkpoint

    entry = _resolve_model(args.model)
    if entry["to_hf"] is None:
        raise NotImplementedError(
            f"{args.model!r} has no to_hf converter in the model registry"
        )
    config = entry["config"]
    template = jax.eval_shape(
        entry["model_cls"](config).init, jax.random.key(0)
    )
    loaded = load_checkpoint(args.input, tag=args.tag, model=template)
    if loaded is None:
        raise FileNotFoundError(f"no checkpoint tag {args.tag} under {args.input}")
    sd = entry["to_hf"](loaded["model"], config)
    save_hf_state_dict(sd, args.output, config)
    if getattr(args, "include_optimizer", False):
        export_optimizer_state(args, entry, template)
    logger.info("wrote HF checkpoint to %s", args.output)


def export_optimizer_state(args, entry, param_template) -> None:
    """Export AdamW state alongside the HF weights (the role of the
    reference's ZeRO-state conversion CLI,
    ``optimizer/convert_zero_checkpoints.py:176`` — which must merge per-dp
    shards; global arrays dissolve that, leaving the HF-naming translation).

    Documented layout, under ``<output>/optimizer/``:

    - ``optimizer-*.safetensors`` (~5GB shards + index.json when split):
      fp32 tensors keyed ``<kind>::<hf_param_name>`` where kind ∈
      {``master``, ``mu``, ``nu``} — fp32 master weights (absent when the
      run used pure-bf16 state), Adam first and second moments. Each tensor
      is laid out exactly like its weight in the HF export (same
      transposes/fusions applied, elementwise correspondence preserved).
    - ``optimizer.json``: {"kinds": [...], "model": ..., "format": 1}.
    """
    from neuronx_distributed_llama3_2_tpu.checkpoint import load_checkpoint
    from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
        OptimizerState,
    )

    config = entry["config"]
    import jax

    step_t = jax.ShapeDtypeStruct((), "int32")
    with_master = OptimizerState(
        step=step_t, master=param_template, mu=param_template,
        nu=param_template,
    )
    without_master = OptimizerState(
        step=step_t, master=None, mu=param_template, nu=param_template
    )
    loaded = None
    for template in (with_master, without_master):
        try:
            loaded = load_checkpoint(
                args.input, tag=args.tag, optimizer=template
            )
            break
        except (KeyError, FileNotFoundError, ValueError):
            continue
    if loaded is None or loaded.get("optimizer") is None:
        raise FileNotFoundError(
            f"checkpoint tag {args.tag} under {args.input} has no optimizer "
            f"state (was it written with save_checkpoint(optimizer=...)?)"
        )
    opt = loaded["optimizer"]
    kinds = {"mu": opt.mu, "nu": opt.nu}
    if opt.master is not None:
        kinds["master"] = opt.master
    sd: Dict[str, Any] = {}
    for kind, tree in kinds.items():
        # moments/master share the params' tree structure, so the family's
        # to_hf applies the identical layout transforms — elementwise
        # correspondence with the exported weights is preserved
        for name, value in entry["to_hf"](tree, config).items():
            sd[f"{kind}::{name}"] = value
    out = os.path.join(args.output, "optimizer")
    _write_sharded_fp32(sd, out, base="optimizer")
    with open(os.path.join(out, "optimizer.json"), "w") as f:
        json.dump(
            {
                "format": 1,
                "model": args.model,
                "kinds": sorted(kinds),
                "step": int(opt.step),
            },
            f,
            indent=2,
        )
    logger.info("wrote optimizer export (%s) to %s", ", ".join(sorted(kinds)), out)


def _write_sharded_fp32(sd: Dict[str, Any], path: str, base: str) -> None:
    """fp32 safetensors with the same ~5GB shard convention as the weight
    export (no dtype cast — optimizer state is meaningful only in fp32)."""
    import numpy as np

    _write_sharded_safetensors(
        sd,
        path,
        base=base,
        itemsize=4,
        cast=lambda v: np.ascontiguousarray(np.asarray(v, np.float32)),
    )


def strip_optimizer(args) -> None:
    import jax

    from neuronx_distributed_llama3_2_tpu.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    entry = _resolve_model(args.model)
    template = jax.eval_shape(
        entry["model_cls"](entry["config"]).init, jax.random.key(0)
    )
    loaded = load_checkpoint(args.input, tag=args.tag, model=template)
    if loaded is None:
        raise FileNotFoundError(f"no checkpoint tag {args.tag} under {args.input}")
    save_checkpoint(
        args.output, tag=args.out_tag or args.tag, model=loaded["model"]
    )
    logger.info(
        "wrote model-only checkpoint %s/%s", args.output, args.out_tag or args.tag
    )


def copy_tag(args) -> None:
    """Offline tag copy/retag between checkpoint roots (fs ↔ S3), optimizer
    state included, no template needed. What remains of the reference's
    nxd_convert_zero_checkpoints CLI under GSPMD: dp/tp/pp resharding needs
    no offline step (global arrays reshard at load via specs), so the tool
    moves storage location and tag name."""
    from neuronx_distributed_llama3_2_tpu.checkpoint import copy_checkpoint

    out = copy_checkpoint(args.input, args.tag, args.output, args.out_tag)
    logger.info("copied to %s/%s", args.output, out)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument(
        "--direction",
        required=True,
        choices=["hf-to-native", "native-to-hf", "strip-optimizer", "copy-tag"],
    )
    p.add_argument(
        "--model",
        default=None,
        help="model registry key (any family's *_CONFIGS name); "
        "not needed for copy-tag",
    )
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--tag", default="latest", help="native checkpoint tag")
    p.add_argument("--out-tag", default=None)
    p.add_argument(
        "--include-optimizer",
        action="store_true",
        help="native-to-hf only: also export AdamW state (fp32 master + "
        "moments) to <output>/optimizer/ — see export_optimizer_state",
    )
    args = p.parse_args(argv)
    if args.direction != "copy-tag" and args.model is None:
        p.error(f"--model is required for --direction {args.direction}")
    {
        "hf-to-native": hf_to_native,
        "native-to-hf": native_to_hf,
        "strip-optimizer": strip_optimizer,
        "copy-tag": copy_tag,
    }[args.direction](args)


if __name__ == "__main__":
    main()
