"""Find a serving cell's knee once, on the chip: the highest offered rate at
which the stated share of requests meets both latency limits and no backlog
grows.

    chiprun -- python3 benchmarks/tools/knee_sweep.py <workload> --rates 2,3,4,5 --seconds 25

One process builds the engine once and serves one window per rate, lowest
first (the prefix cache carries over, as in a server that stays up). The
limits and the attainment share come from the traffic file's ``"limits"``.
Write 0.8 x the knee into ``arrivals.rate_rps`` of the traffic file and the
sweep into PERF.md. This is a tool for defining a cell; a benchmark run never
searches for a rate."""

import argparse
import asyncio
import copy
import dataclasses
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec, stats  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from neuronx_distributed_llama3_2_tpu.utils.runtime import enable_compile_cache, require_tpu

    print(require_tpu(), flush=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    family = spec.load_family(cell.config["family"])
    split = {}
    engine, model_cfg, checked = serving.build(cell, family, args.seed, False, False, split)
    print(f"set-up {split}; check {checked}", flush=True)
    limits = cell.traffic["limits"]
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"]["rate_rps"] = rate
        at_rate = dataclasses.replace(cell, traffic=traffic)
        t0 = time.perf_counter()
        samples, window, _ = asyncio.run(serving.drive(
            at_rate, engine, model_cfg.vocab_size, args.seed, args.seconds, False))
        due = [s for s in samples if s.measured]
        ttft = serving.ttft_ms(due)
        tpot = serving.tpot_ms(due)
        met = sum(
            1 for s in due
            if s.done is not None and s.error is None and s.tokens > 1
            and (s.first_token - s.due) * 1e3 <= limits["ttft_ms"]
            and (s.last_token - s.first_token) * 1e3 / (s.tokens - 1) <= limits["tpot_ms"]
        )
        late = [(s.sent - s.due) * 1e3 for s in due if s.sent is not None]
        half = len(due) // 2
        first, second = serving.ttft_ms(due[:half]), serving.ttft_ms(due[half:])
        ms = lambda xs, q=50: f"{stats.percentile(xs, q):.0f}" if xs else "-"  # noqa: E731
        print(
            f"rate {rate:.2f} req/s: due {len(due)}, completed "
            f"{sum(1 for s in due if s.done is not None)}, failed "
            f"{sum(1 for s in due if s.error)}, met both limits {met} "
            f"({100.0 * met / max(len(due), 1):.1f} %), ttft p50/p90 "
            f"{ms(ttft)}/{ms(ttft, 90)} ms "
            f"(first half p50 {ms(first)}, second half {ms(second)}), "
            f"tpot p50/p90 {ms(tpot)}/{ms(tpot, 90)} ms, "
            f"generator late p90 {ms(late, 90)} ms, "
            f"wall {time.perf_counter() - t0:.0f} s",
            flush=True,
        )


if __name__ == "__main__":
    main()
