"""One chip-side inference measurement, one JSON line.

Builds a random-init engine for a registry model and runs exactly one of
the staged benchmarks from
:mod:`neuronx_distributed_llama3_2_tpu.inference.runner` (run it on the chip
through the chip tool, one command per stage):

- ``prefill``: chip-side TTFT estimator (``benchmark_prefill_on_device``) —
  amortizes the per-request host round trip out of the prefill number.
- ``generate``: end-to-end p50/p90/p99 TTFT + per-token latency
  (reference latency report format, benchmark.py:9-66).
- ``churn``: continuous-batching throughput under staggered admissions,
  asserting no program compiles under traffic.

Random weights are fine for latency work — the compiled programs are
shape-dependent only (the reference's latency benches also run on whatever
checkpoint is handy; accuracy has its own gate, runner.py check_accuracy).

Usage::

    python scripts/infer_bench_stage.py --stage prefill --model llama3.2-1b
    python scripts/infer_bench_stage.py --stage churn --model llama3.2-1b
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", required=True,
                    choices=("prefill", "generate", "churn"))
    ap.add_argument("--model", default="llama3.2-1b")
    # churn needs >= 2 slots or the staggered-admission regime it gates on
    # (multi-slot admissions/completions mid-run) degenerates to sequential
    ap.add_argument("--batch", type=int, default=None,
                    help="default: 1 (prefill/generate), 4 (churn)")
    ap.add_argument("--max-seq-len", type=int, default=1024)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="virtual CPU mesh (testing only)")
    args = ap.parse_args()

    import jax

    if args.cpu_devices:
        from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

        set_cpu_devices(args.cpu_devices)

    from neuronx_distributed_llama3_2_tpu.inference import InferenceEngine
    from neuronx_distributed_llama3_2_tpu.inference import runner as bench_runner
    from neuronx_distributed_llama3_2_tpu.models import resolve_model

    if args.batch is None:
        args.batch = 4 if args.stage == "churn" else 1

    entry = resolve_model(args.model)
    config = entry["config"]
    params = entry["model_cls"](config).init(jax.random.key(args.seed))
    engine = InferenceEngine(
        config, params, max_batch=args.batch, max_seq_len=args.max_seq_len
    )

    if args.stage == "prefill":
        report = bench_runner.benchmark_prefill_on_device(
            engine, prompt_len=args.prompt_len, seed=args.seed
        )
    elif args.stage == "generate":
        report = bench_runner.benchmark_generation(
            engine,
            prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens,
            seed=args.seed,
        )
    else:
        report = bench_runner.benchmark_serving_churn(
            engine,
            prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens,
            seed=args.seed,
        )

    gate_failure = None
    if args.stage == "churn" and report["compiled_under_traffic"] != 0:
        gate_failure = (
            f"compiled {report['compiled_under_traffic']} programs under "
            "traffic — serving precompile regression"
        )

    # the record prints even when the gate fails: a regression must still
    # yield the measured numbers, not just an exception tail
    print(json.dumps({
        "stage": args.stage,
        "model": args.model,
        "chip": str(jax.devices()[0]),
        **({"gate_failure": gate_failure} if gate_failure else {}),
        **report,
    }), flush=True)
    if gate_failure:
        raise SystemExit(gate_failure)


if __name__ == "__main__":
    main()
