"""Paged KV-cache bench: prefix-sharing workload, one BENCH JSON line.

Runs the acceptance workload for the paged serving path (docs/serving.md):
N requests sharing a long common prefix with unique tails, greedy decode,
through :class:`~neuronx_distributed_llama3_2_tpu.serving.PagedServingEngine`
and (for the equivalence gate) the dense
:class:`~neuronx_distributed_llama3_2_tpu.inference.ContinuousBatchingEngine`.
The record carries the prefix-skip fraction, block-pool stats, preemption
count, and wall-clock for both paths.

Gates (record still prints on failure, like infer_bench_stage.py):

- token-identical greedy outputs, paged vs dense
- >= ``--min-skip`` of prompt tokens admitted by prefix reference
  (default 0.5 — the ISSUE acceptance bar; the default 16x256+32 workload
  actually lands ~0.83)
- tiered-KV churn leg (``--skip-spill`` to omit): a multi-tenant
  workload sharing 8 system prompts over an eviction-forcing pool, spill
  on vs off — restore hit rate > 0, byte-identical outputs, and
  tokens/step no worse than the recompute baseline (5% floor)

Usage::

    python scripts/kv_block_bench.py            # 16 req x 256-token prefix
    python scripts/kv_block_bench.py --smoke    # seconds-scale CPU check
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def build_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale workload (CI); overrides the "
                    "workload knobs below")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prefix-tokens", type=int, default=256)
    ap.add_argument("--tail-tokens", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=160)
    ap.add_argument("--min-skip", type=float, default=0.5)
    ap.add_argument("--skip-dense", action="store_true",
                    help="skip the dense run (no equivalence gate)")
    ap.add_argument("--skip-spill", action="store_true",
                    help="skip the tiered-KV spill churn leg")
    ap.add_argument("--churn-requests", type=int, default=None,
                    help="requests in the spill churn leg (default 64, "
                    "24 smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="virtual CPU mesh (testing only)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = 4
        args.prefix_tokens = 24
        args.tail_tokens = 4
        args.max_new_tokens = 4
        args.max_seq_len = 64
        args.block_size = 8
        args.num_blocks = 64
    return args


def run_spill_leg(args: argparse.Namespace, config, params, gen) -> dict:
    """Tiered-KV churn leg: many users sharing 8 long system prompts over
    a pool deliberately too small to keep them all resident, run through
    a spill-disabled (recompute) engine and a spill-enabled twin.
    ``restore_crossover`` is forced sky-high: tiny-bench prefill FLOPs
    are nearly free, and the leg measures the restore mechanism —
    byte-identity, hit rate, and tokens/step vs recompute — not the
    pricing policy (docs/serving.md "Tiered KV storage")."""
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.inference import InferenceEngine
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    n = args.churn_requests or (24 if args.smoke else 64)
    rng = np.random.default_rng(args.seed + 1)
    system = [
        rng.integers(0, config.vocab_size, size=(24,)).tolist()
        for _ in range(8)
    ]
    prompts = [
        system[i % 8]
        + rng.integers(0, config.vocab_size, size=(int(rng.integers(4, 9)),))
        .tolist()
        for i in range(n)
    ]

    runs = {}
    for spill in (False, True):
        eng = PagedServingEngine(
            InferenceEngine(
                config, params, max_batch=4, max_seq_len=64,
                buckets=[16, 32],
            ),
            gen,
            PagedConfig(
                block_size=8, num_blocks=28,
                spill_enabled=spill,
                host_tier_bytes=(1 << 30) if spill else 0,
                restore_crossover=1e9 if spill else 1.0,
                prewarm=True,
            ),
        )
        for p in prompts:
            eng.submit(p)
        t0 = time.perf_counter()
        outs = eng.run_to_completion()
        wall = time.perf_counter() - t0
        m = eng.metrics
        steps = eng._step_index
        runs[spill] = {
            "outs": outs,
            "wall_s": round(wall, 3),
            "tokens_per_step": (
                sum(len(o) for o in outs.values()) / steps if steps else 0.0
            ),
            "metrics": m,
        }
    base, spl = runs[False], runs[True]
    ms = spl["metrics"]
    rec = {
        "churn_requests": n,
        "churn_base_wall_s": base["wall_s"],
        "churn_spill_wall_s": spl["wall_s"],
        "churn_base_tokens_per_step": round(base["tokens_per_step"], 3),
        "churn_spill_tokens_per_step": round(spl["tokens_per_step"], 3),
        "churn_blocks_spilled": ms.blocks_spilled,
        "churn_blocks_restored": ms.blocks_restored,
        "churn_restore_hits": ms.restore_hits,
        "churn_restore_hit_rate": ms.snapshot()["restore_hit_rate"],
        "churn_prefill_chunks_base": base["metrics"].prefill_chunks,
        "churn_prefill_chunks_spill": ms.prefill_chunks,
        "churn_spill_equivalent": base["outs"] == spl["outs"],
    }
    failures = []
    if not rec["churn_spill_equivalent"]:
        failures.append("spill churn outputs diverge from recompute baseline")
    if not ms.restore_hits > 0:
        failures.append(
            f"spill churn never restored ({ms.blocks_spilled} spilled)"
        )
    if base["tokens_per_step"] and (
        spl["tokens_per_step"] < 0.95 * base["tokens_per_step"]
    ):
        failures.append(
            "spill churn tokens/step regressed >5%: "
            f"{spl['tokens_per_step']:.3f} vs {base['tokens_per_step']:.3f}"
        )
    return rec, failures


def run_bench(args: argparse.Namespace) -> dict:
    import jax
    import numpy as np

    if args.cpu_devices:
        from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

        set_cpu_devices(args.cpu_devices)

    from neuronx_distributed_llama3_2_tpu.inference import (
        ContinuousBatchingEngine,
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.models import resolve_model
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    entry = resolve_model(args.model)
    config = dataclasses.replace(entry["config"], max_seq_len=args.max_seq_len)
    params = entry["model_cls"](config).init(jax.random.key(args.seed))
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)

    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, config.vocab_size, size=(args.prefix_tokens,))
    prompts = [
        shared.tolist()
        + rng.integers(0, config.vocab_size, size=(args.tail_tokens,)).tolist()
        for _ in range(args.requests)
    ]

    def fresh_engine():
        return InferenceEngine(
            config, params,
            max_batch=args.max_batch, max_seq_len=args.max_seq_len,
        )

    paged = PagedServingEngine(
        fresh_engine(), gen,
        PagedConfig(
            block_size=args.block_size, num_blocks=args.num_blocks, prewarm=True,
        ),
    )
    for p in prompts:
        paged.submit(p)
    t0 = time.perf_counter()
    out_paged = paged.run_to_completion()
    paged_s = time.perf_counter() - t0

    equivalent = None
    dense_s = None
    if not args.skip_dense:
        dense = ContinuousBatchingEngine(fresh_engine(), gen)
        for p in prompts:
            dense.submit(p)
        t0 = time.perf_counter()
        out_dense = dense.run_to_completion()
        dense_s = time.perf_counter() - t0
        equivalent = out_dense == out_paged

    m = paged.metrics
    record = {
        "bench": "kv_block",
        "model": args.model,
        "chip": str(jax.devices()[0]),
        "smoke": bool(args.smoke),
        "requests": args.requests,
        "prefix_tokens": args.prefix_tokens,
        "tail_tokens": args.tail_tokens,
        "max_new_tokens": args.max_new_tokens,
        "max_batch": args.max_batch,
        "paged_wall_s": round(paged_s, 3),
        "dense_wall_s": None if dense_s is None else round(dense_s, 3),
        "dense_equivalent": equivalent,
        **m.snapshot(paged.allocator, paged.index),
    }
    failures = []
    if equivalent is False:
        failures.append("paged outputs diverge from dense greedy outputs")
    if m.prefix_skip_fraction() < args.min_skip:
        failures.append(
            f"prefix skip {m.prefix_skip_fraction():.3f} < {args.min_skip}"
        )
    if not args.skip_spill:
        spill_rec, spill_failures = run_spill_leg(args, config, params, gen)
        record.update(spill_rec)
        failures.extend(spill_failures)
    if failures:
        record["gate_failure"] = "; ".join(failures)
    return record


def main() -> None:
    args = build_args()
    record = run_bench(args)
    # the record prints even when a gate fails: a regression must still
    # yield the measured numbers, not just an exception tail
    print(json.dumps(record), flush=True)
    if record.get("gate_failure"):
        raise SystemExit(record["gate_failure"])


if __name__ == "__main__":
    main()
