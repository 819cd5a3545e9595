"""Interleaved executors: program size must be O(1) in M·V.

VERDICT r4 #4 "done" criterion. Both interleaved paths now execute the
host-simulated plan as (R, pp) integer tables scanned by a uniform
``lax.scan`` rotation body (pipeline/model.py) — the analogue of the
reference's constant-size per-task schedule loop
(/root/reference/src/neuronx_distributed/pipeline/scheduler.py:256).
This script compiles the forward (``InterleavedRotationPlan`` path) and
the train step (``Interleaved1F1BPlan`` memory-bounded backward) at
growing M and reports compiled HLO instruction counts + compile seconds:
bounded ⇔ instruction count is flat in M (the scan trip count grows, the
program does not).

Usage: python scripts/vpp_compile_bound.py [--pp 2] [--chunks 4]
Prints ONE JSON line; table in docs/interleaved_vpp.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

set_cpu_devices(8)

import jax.numpy as jnp
import numpy as np


def measure(pp: int, V: int, M: int, fwd_only: bool) -> dict:
    from neuronx_distributed_llama3_2_tpu.models.llama import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
    from neuronx_distributed_llama3_2_tpu.parallel.layers import shard_pytree
    from neuronx_distributed_llama3_2_tpu.pipeline.model import PipelinedCausalLM

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(pipeline_model_parallel_size=pp)

    cfg = dataclasses.replace(
        LLAMA_CONFIGS["tiny"], num_layers=pp * V, remat="none"
    )
    model = PipelinedCausalLM(
        LlamaForCausalLM(cfg),
        num_microbatches=M,
        schedule="interleaved",
        num_model_chunks=V,
        memory_bounded_backward=not fwd_only,
    )
    params = shard_pytree(jax.jit(model.init)(jax.random.key(0)), model.specs())
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (M, 32)),
        jnp.int32,
    )

    if fwd_only:
        fn = jax.jit(lambda p, i: model(p, i))
        args = (params, ids)
    else:
        fn = jax.jit(lambda p, i, l: model.loss_and_grad(p, i, l))
        args = (params, ids, ids)

    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    text = compiled.as_text()
    n_instr = sum(
        1 for ln in text.splitlines() if "=" in ln and not ln.lstrip().startswith("//")
    )
    parallel_state.destroy_model_parallel()
    return {
        "M": M,
        "hlo_instructions": n_instr,
        "compile_s": round(dt, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--microbatches", type=int, nargs="+", default=[16, 32])
    args = ap.parse_args()

    out = {"bench": "vpp_compile_bound", "pp": args.pp, "V": args.chunks}
    for path, fwd_only in (("forward", True), ("train_1f1b", False)):
        rows = [measure(args.pp, args.chunks, m, fwd_only)
                for m in args.microbatches]
        lo, hi = rows[0], rows[-1]
        out[path] = {
            "rows": rows,
            # flat ⇔ doubling M adds ~0 instructions (scan trip count only)
            "instr_growth_ratio": round(
                hi["hlo_instructions"] / max(lo["hlo_instructions"], 1), 3
            ),
        }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
