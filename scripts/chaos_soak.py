"""Chaos soak for the paged serving engine: one BENCH JSON line.

Drives a seeded randomized arrival schedule through the engine twice —
once fault-free (the greedy baseline), once under a chaos
:class:`~neuronx_distributed_llama3_2_tpu.serving.FaultInjector` firing
every fault class (device errors, NaN logits, drafter bugs, transient
alloc failures, transfer latency, host-tier corruption) — with every
serving feature on: async lookahead, speculation, chunked prefill, a
pool tight enough to preempt, tiered KV spill (both runs — a third of
the prompts share a system prefix so the tight pool keeps spilling and
restoring it, giving the ``host_tier`` fault restore attempts to
corrupt), periodic strict invariant audits, the degradation ladder. A
host-tier fault is absorbed like a drafter bug: the spilled run is
invalidated inside its own failure domain and the request re-prefills,
so the parity gate below also proves restore-fallback changes no
tokens.

Gates (record still prints on failure, like kv_block_bench.py):

- every fault class fired at least once
- **parity of unaffected requests**: every request that survived the
  chaos run is token-identical to the fault-free baseline, and every
  faulted request surfaces ``status == "failed"`` with error detail and
  a baseline-prefix partial output
- zero leaked blocks and a clean invariant audit at teardown
- zero audit violations during the run (strict audits ran at every
  finish/preempt/fail transition)

Usage::

    python scripts/chaos_soak.py            # 24 requests, every fault class
    python scripts/chaos_soak.py --smoke    # seconds-scale CPU check
"""
from __future__ import annotations

import argparse
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
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--arrival-span", type=int, default=120,
                    help="steps over which request arrivals spread")
    ap.add_argument("--max-new-tokens", type=int, default=12)
    ap.add_argument("--max-seq-len", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed (prompts + arrivals)")
    ap.add_argument("--fault-seed", type=int, default=7)
    ap.add_argument("--drafter-rate", type=float, default=0.05)
    ap.add_argument("--alloc-rate", type=float, default=0.02)
    ap.add_argument("--latency-rate", type=float, default=0.05)
    ap.add_argument("--host-tier-rate", type=float, default=0.2,
                    help="per-restore-attempt host-tier corruption rate")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="virtual CPU mesh (testing only)")
    ap.add_argument("--trace-dir", default=os.environ.get("SERVING_TRACE_DIR"),
                    help="directory for graftscope artifacts (Chrome trace "
                    "JSON + prometheus text); defaults to $SERVING_TRACE_DIR; "
                    "unset = no artifacts")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = 8
        args.arrival_span = 40
        args.max_new_tokens = 8
    return args


def run_bench(args: argparse.Namespace) -> dict:
    import dataclasses

    import jax
    import numpy as np

    if args.cpu_devices:
        from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

        set_cpu_devices(args.cpu_devices)

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.models import resolve_model
    from neuronx_distributed_llama3_2_tpu.serving import (
        FAULT_KINDS,
        FaultInjector,
        FaultPlan,
        PagedConfig,
        PagedServingEngine,
        audit_engine,
    )

    entry = resolve_model(args.model)
    config = dataclasses.replace(entry["config"], max_seq_len=args.max_seq_len)
    params = entry["model_cls"](config).init(jax.random.key(args.seed))
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens)

    rng = np.random.default_rng(args.seed)
    lengths = rng.integers(3, 32, size=args.requests)
    # cycled system prefixes (3 blocks each at the default block_size=4):
    # the reuse distance plus the tight pool evicts each one between its
    # uses, so the spill tier keeps restoring them — the host_tier fault
    # class needs those restore attempts
    shared = [
        rng.integers(0, config.vocab_size, size=(12,)).tolist()
        for _ in range(4)
    ]
    prompts = []
    for i, n in enumerate(lengths):
        if i % 2 == 1:  # prefix-sharing half so spill/restore engages
            prompts.append(
                shared[i % 4]
                + rng.integers(0, config.vocab_size, size=(int(n),)).tolist()
            )
        elif i % 2 == 0:  # repetitive half so speculation engages
            pat = rng.integers(1, 9, size=3).tolist()
            prompts.append((pat * (int(n) // 3 + 1))[: int(n)])
        else:
            prompts.append(
                rng.integers(0, config.vocab_size, size=(int(n),)).tolist()
            )
    arrivals = np.sort(
        rng.integers(0, args.arrival_span, size=args.requests)
    ).tolist()

    paged_cfg = PagedConfig(
        block_size=args.block_size, num_blocks=args.num_blocks,
        decode_reserve_blocks=1, prefill_chunk_tokens=8,
        # spill on BOTH runs (parity compares spill-vs-spill); crossover
        # forced sky-high because tiny-model prefill FLOPs are ~free
        spill_enabled=True, host_tier_bytes=1 << 30, restore_crossover=1e9,
        spec_draft_tokens=4, stall_step_limit=500, audit_interval=8,
        audit_debug=True, degrade_after_faults=3, degrade_window_steps=32,
        degrade_recover_steps=16,
        # tracing rides the chaos run unconditionally: the parity gate vs
        # the untraced baseline doubles as a zero-interference check under
        # the full feature matrix, and --trace-dir banks the timeline
        trace_enabled=True, trace_buffer_steps=512,
    )
    # a scheduled entry per class guarantees coverage whatever the rates
    plan = FaultPlan(
        seed=args.fault_seed,
        drafter_rate=args.drafter_rate, alloc_rate=args.alloc_rate,
        latency_rate=args.latency_rate, latency_ms=0.1,
        host_tier_rate=args.host_tier_rate,
        schedule=(
            (5, "device"), (15, "nan"), (20, "drafter"),
            (25, "alloc"), (30, "latency"), (0, "host_tier"),
        ),
    )

    def drive(injector):
        # baseline runs untraced: the parity-of-unaffected gate then also
        # proves tracing changed no tokens
        cfg = paged_cfg if injector is not None else dataclasses.replace(
            paged_cfg, audit_interval=0, audit_debug=False,
            trace_enabled=False,
        )
        paged = PagedServingEngine(
            InferenceEngine(
                config, params,
                max_batch=args.max_batch, max_seq_len=args.max_seq_len,
            ),
            gen, cfg, injector=injector,
        )
        steps, next_req, alive = 0, 0, True
        t0 = time.perf_counter()
        while alive or next_req < args.requests:
            while next_req < args.requests and arrivals[next_req] <= steps:
                paged.submit(prompts[next_req])
                next_req += 1
            alive = paged.step()
            steps += 1
            if steps >= 20000:
                raise RuntimeError("chaos soak did not converge")
        return paged, steps, time.perf_counter() - t0

    baseline, base_steps, base_s = drive(None)
    base_out = {rid: r.out for rid, r in baseline._finished.items()}
    chaos, chaos_steps, chaos_s = drive(FaultInjector(plan))

    failures = []
    missing = [k for k in FAULT_KINDS if chaos.injector.counts[k] < 1]
    if missing:
        failures.append(f"fault classes never fired: {missing}")

    n_finished = n_failed = 0
    for rid, req in chaos._finished.items():
        info = chaos.request_info(rid)
        if info["status"] == "failed":
            n_failed += 1
            if not info["error"]:
                failures.append(f"rid {rid} failed without error detail")
            if req.out != base_out[rid][: len(req.out)]:
                failures.append(
                    f"rid {rid} (failed) diverged from the baseline prefix"
                )
        else:
            n_finished += 1
            if req.out != base_out[rid]:
                failures.append(
                    f"rid {rid} (unaffected) not token-identical to baseline"
                )
    if len(chaos._finished) != args.requests:
        failures.append(
            f"{len(chaos._finished)} terminal requests != {args.requests}"
        )
    if n_failed == 0:
        failures.append("no request failed under device+nan chaos")
    if n_finished == 0:
        failures.append("no request survived the chaos run")

    leaks = chaos.allocator.leak_check()
    if chaos.allocator.active_blocks != 0 or leaks:
        failures.append(f"leaked blocks at teardown: {leaks}")
    violations = audit_engine(chaos)
    if violations:
        failures.append(f"invariant violations at teardown: {violations}")
    if chaos.metrics.audit_violations:
        failures.append(
            f"{chaos.metrics.audit_violations} audit violations during run"
        )

    m = chaos.metrics
    record = {
        "bench": "chaos_soak",
        "model": args.model,
        "chip": str(jax.devices()[0]),
        "smoke": bool(args.smoke),
        "requests": args.requests,
        "baseline_steps": base_steps,
        "baseline_wall_s": round(base_s, 3),
        "chaos_steps": chaos_steps,
        "chaos_wall_s": round(chaos_s, 3),
        "finished": n_finished,
        "failed": n_failed,
        "faults_by_kind": dict(chaos.injector.counts),
        **m.snapshot(chaos.allocator, chaos.index),
    }
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        record["trace_artifact"] = chaos.export_trace(
            os.path.join(args.trace_dir, "chaos_soak_trace.json")
        )
        prom_path = os.path.join(args.trace_dir, "chaos_soak_metrics.prom")
        with open(prom_path, "w") as f:
            f.write(m.prometheus(chaos.allocator, chaos.index))
        record["prometheus_artifact"] = prom_path
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
