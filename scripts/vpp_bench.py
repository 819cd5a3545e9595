"""Interleaved-VPP measurement: chunked SPMD rotation vs gpipe vs 1F1B.

Produces the table recorded in docs/interleaved_vpp.md (VERDICT r2 item 3:
turn the scheduler's "cannot profit under SPMD" analysis into numbers).
Runs on the virtual CPU mesh; wall-clock there includes the per-rotation
dispatch overheads the lock-step cost model ignores, so both the model's
prediction and reality are reported.

Usage: python scripts/vpp_bench.py [--pp 4] [--microbatches 16]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax

from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

set_cpu_devices(8)

import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=16)
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    from neuronx_distributed_llama3_2_tpu.models.llama import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
    from neuronx_distributed_llama3_2_tpu.parallel.layers import shard_pytree
    from neuronx_distributed_llama3_2_tpu.pipeline.model import PipelinedCausalLM
    from neuronx_distributed_llama3_2_tpu.pipeline.scheduler import (
        InterleavedRotationPlan,
    )

    cfg = dataclasses.replace(
        LLAMA_CONFIGS["tiny"],
        num_layers=args.layers,
        hidden_size=args.hidden,
        num_heads=4,
        num_kv_heads=2,
        head_dim=args.hidden // 4,
        intermediate_size=args.hidden * 4,
        max_seq_len=args.seq,
        dtype=jnp.float32,
        remat="none",
    )
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.key(0))
    M = args.microbatches
    gbs = 2 * M
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (gbs, args.seq)),
        jnp.int32,
    )

    def bench(pm, grad_fn):
        pv = shard_pytree(pm.to_pipeline(params), pm.specs())
        lowered = jax.jit(grad_fn).lower(pv, ids, ids)
        compiled = lowered.compile()
        flops = compiled.cost_analysis().get("flops", float("nan"))
        t0 = time.perf_counter()
        out = compiled(pv, ids, ids)
        jax.block_until_ready(out)
        compile_plus_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = compiled(pv, ids, ids)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.iters
        loss = out[0] if isinstance(out, tuple) else out
        return dt, flops, float(jnp.asarray(loss).reshape(-1)[0])

    rows = []
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size=args.pp
    )

    gp = PipelinedCausalLM(model, num_microbatches=M, schedule="gpipe")
    dt, fl, loss = bench(gp, jax.value_and_grad(gp.loss))
    rows.append(("gpipe", 1, dt, fl, loss, M + args.pp - 1))

    fb = PipelinedCausalLM(model, num_microbatches=M, schedule="1f1b")
    dt, fl, loss = bench(fb, fb.loss_and_grad)
    rows.append(("1f1b", 1, dt, fl, loss, M + 2 * (args.pp - 1)))

    for V in (1, 2, 4):
        if args.layers % (args.pp * V):
            continue
        pm = PipelinedCausalLM(
            model,
            num_microbatches=M,
            schedule="interleaved",
            num_model_chunks=V,
        )
        plan = InterleavedRotationPlan(M, V, args.pp)
        dt, fl, loss = bench(pm, jax.value_and_grad(pm.loss))
        rows.append((f"interleaved", V, dt, fl, loss, plan.num_rotations))

    base = rows[0][2]
    print(
        f"\npp={args.pp} M={M} L={args.layers} hidden={args.hidden} "
        f"seq={args.seq} gbs={gbs} (8-device CPU mesh, dp={8 // args.pp})"
    )
    print(
        f"{'schedule':<14}{'V':>3}{'rotations':>10}{'step_ms':>10}"
        f"{'vs gpipe':>10}{'Gflop':>8}{'loss':>10}"
    )
    for name, V, dt, fl, loss, rot in rows:
        print(
            f"{name:<14}{V:>3}{rot:>10}{dt * 1e3:>10.1f}"
            f"{dt / base:>10.2f}{fl / 1e9:>8.2f}{loss:>10.4f}"
        )
    # lock-step cost model prediction (compute units ∝ rotations × stage len)
    print("\ncost-model (compute units = rotations × layers-per-stage × pp):")
    for V in (1, 2, 4):
        if args.layers % (args.pp * V):
            continue
        plan = InterleavedRotationPlan(M, V, args.pp)
        comp, perm = plan.cost_model(args.layers // args.pp)
        print(
            f"  V={V}: rotations={plan.num_rotations} "
            f"idle_lane_rotations={plan.idle_lane_rotations} "
            f"compute_units={comp} permutes={perm}"
        )
    print(
        json.dumps(
            {
                "rows": [
                    {
                        "schedule": n,
                        "chunks": V,
                        "rotations": rot,
                        "step_ms": round(dt * 1e3, 1),
                        "flops": fl,
                        "loss": round(loss, 5),
                    }
                    for n, V, dt, fl, loss, rot in rows
                ]
            }
        )
    )


if __name__ == "__main__":
    main()
