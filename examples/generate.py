"""Launchable text generation + latency report.

TPU-native equivalent of the reference's inference run scripts
(``examples/inference/run_llama.py`` / ``dbrx_runner.py`` /
``run_llama_speculative.py``: trace → load → generate → benchmark). Loads
weights from an HF checkpoint directory (any registry family with a
``from_hf`` converter) or from a native checkpoint tag, builds the bucketed
AOT engine, generates, and prints the p50/p90/p99 latency report
(reference benchmark.py:9-66 format).

Examples::

    # HF weights + tokenizer, sampled generation
    python examples/generate.py --model llama3.2-1b --hf-dir /ckpts/llama32-1b \
        --prompt "The capital of France is" --max-new-tokens 64 \
        --temperature 0.7 --top-p 0.9

    # native checkpoint, greedy, raw token ids
    python examples/generate.py --model tiny --ckpt-dir /tmp/run --tag latest \
        --prompt-ids 12,99,4,7 --greedy --on-device-steps 16
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", required=True, help="model registry key")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--hf-dir", help="HF checkpoint directory")
    src.add_argument("--ckpt-dir", help="native checkpoint root")
    src.add_argument(
        "--random-init", action="store_true",
        help="random weights (smoke/latency runs)",
    )
    p.add_argument("--tag", default="latest", help="native checkpoint tag")
    p.add_argument("--prompt", help="text prompt (needs --hf-dir tokenizer)")
    p.add_argument("--prompt-ids", help="comma-separated token ids")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--greedy", action="store_true")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--on-device-steps", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument(
        "--aot", action="store_true",
        help="pre-compile every bucket program before the first request",
    )
    p.add_argument(
        "--cpu-devices", type=int, default=0,
        help="force an n-device virtual CPU mesh (testing)",
    )
    return p.parse_args()


def main():
    args = parse_args()
    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache,
        set_cpu_devices,
    )

    if args.cpu_devices:
        set_cpu_devices(args.cpu_devices)
    enable_compile_cache()

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
        SamplingConfig,
    )
    from neuronx_distributed_llama3_2_tpu.models import resolve_model
    from neuronx_distributed_llama3_2_tpu.utils.logger import get_logger

    logger = get_logger()
    entry = resolve_model(args.model)
    config = entry["config"]
    if type(config).__name__ == "MllamaConfig":
        raise SystemExit(
            f"{args.model}: multimodal decode needs image inputs; use "
            f"inference.MllamaDecoder from the library instead of this "
            f"text-only CLI."
        )

    tokenizer = None
    if args.hf_dir:
        from neuronx_distributed_llama3_2_tpu.scripts.checkpoint_converter import (
            load_hf_state_dict,
        )

        params = entry["from_hf"](load_hf_state_dict(args.hf_dir), config)
        try:
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(args.hf_dir)
        except Exception:
            logger.warning("no tokenizer under %s; pass --prompt-ids", args.hf_dir)
    elif args.ckpt_dir:
        from neuronx_distributed_llama3_2_tpu.checkpoint import load_checkpoint

        template = jax.eval_shape(
            entry["model_cls"](config).init, jax.random.key(0)
        )
        loaded = load_checkpoint(args.ckpt_dir, tag=args.tag, model=template)
        if loaded is None:
            raise SystemExit(f"no checkpoint {args.tag} under {args.ckpt_dir}")
        params = loaded["model"]
    else:
        params = entry["model_cls"](config).init(jax.random.key(args.seed))

    if args.tp > 1:
        from neuronx_distributed_llama3_2_tpu.parallel.layers import shard_pytree
        from neuronx_distributed_llama3_2_tpu.trainer import TrainingConfig

        tc = TrainingConfig(tensor_parallel_size=args.tp)
        tc.initialize()
        params = shard_pytree(params, entry["model_cls"](config).specs())

    if args.prompt_ids:
        prompt = [int(t) for t in args.prompt_ids.split(",")]
    elif args.prompt:
        if tokenizer is None:
            raise SystemExit("--prompt needs a tokenizer (--hf-dir) — or pass --prompt-ids")
        prompt = tokenizer.encode(args.prompt)
    else:
        raise SystemExit("pass --prompt or --prompt-ids")

    sampling = SamplingConfig(
        greedy=args.greedy,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
    )
    gen = GenerationConfig(
        max_new_tokens=args.max_new_tokens,
        sampling=sampling,
        seed=args.seed,
        on_device_steps=args.on_device_steps,
        eos_token_id=(
            tokenizer.eos_token_id if tokenizer is not None else None
        ),
    )
    engine = InferenceEngine(
        config, params, max_batch=args.batch, max_seq_len=args.max_seq_len
    )
    if args.aot:
        secs = engine.aot_compile(
            sampling=sampling,
            on_device_steps=(args.on_device_steps,) if args.on_device_steps > 1 else (),
        )
        logger.info("AOT-compiled every bucket program in %.1fs", secs)

    result = engine.generate([prompt] * args.batch, gen)
    for i, toks in enumerate(result.sequences):
        text = tokenizer.decode(toks) if tokenizer is not None else toks
        print(f"--- request {i}: {text}")
    print(json.dumps(result.benchmark.report(), indent=2))


if __name__ == "__main__":
    main()
