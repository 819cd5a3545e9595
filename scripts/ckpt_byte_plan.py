"""Checkpoint-write byte accounting at the 70B config (VERDICT r4 #6).

The sharded save protocol (checkpoint/checkpoint.py) writes each chunk
from its replica-0 holder and everything replicated lands on process 0 —
fine when most bytes are sharded, but worth exact accounting before the
v5e-64 target: a leaf sharded over tp only (replicated over pp) has all
its replica-0 shards on the pp=0 slice, concentrating its bytes on the
first host(s), and fully-replicated leaves concentrate on process 0.

This script computes, WITHOUT materializing any array, the exact bytes
each process writes for llama3-70b at tp=8 × pp=8 (64 chips; the
BASELINE.md large-scale layout, reference
run_llama3_70B_tp_pp.sh:52-56 precedent TP=32 PP=8) with ZeRO-1
optimizer state: `jax.eval_shape` over the real pipelined model +
`model.specs()` / `optimizer_state_specs` — the same trees the trainer
shards with — and the checkpoint module's own
:func:`plan_chunk_writers` owner rule (validated against real
multi-process writes in tests/multihost_worker.py).

The per-process table is the deliverable (docs/ckpt_byte_plan.md);
`tests/test_checkpoint.py` keeps the accounting in sync with the model.

Usage: python scripts/ckpt_byte_plan.py [--devices-per-process 4]
Prints ONE JSON line.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

TP, PP = 8, 8


def compute_plan(
    devices_per_process: int = 4,
    model_name: str = "llama3-70b",
    tp: int = TP,
    pp: int = PP,
    num_microbatches: int = 8,
):
    import numpy as np

    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_llama3_2_tpu.checkpoint.checkpoint import (
        plan_chunk_writers,
    )
    from neuronx_distributed_llama3_2_tpu.models.llama import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
    from neuronx_distributed_llama3_2_tpu.pipeline.model import PipelinedCausalLM
    from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
        OptimizerConfig,
        optimizer_state_specs,
    )

    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp
    )
    st = parallel_state.get_parallel_state()
    mesh = st.mesh
    n_dev = int(np.prod(mesh.devices.shape))
    assert n_dev == tp * pp, (n_dev, tp * pp)
    n_proc = n_dev // devices_per_process
    pos = {d: i for i, d in enumerate(mesh.devices.flat)}
    multi_process = jax.process_count() > 1

    def proc_of(dev) -> int:
        # real multi-host: the device KNOWS its process — mesh order may be
        # permuted by create_device_mesh's ICI-topology reordering, so
        # positional attribution would mislabel hosts. The positional model
        # is the single-process SIMULATION only (where all devices report
        # process 0), and assumes the contiguous plain-reshape device order
        # of the simulated mesh.
        if multi_process:
            return dev.process_index
        return pos[dev] // devices_per_process

    model = PipelinedCausalLM(
        LlamaForCausalLM(LLAMA_CONFIGS[model_name]),
        num_microbatches=num_microbatches,
        schedule="1f1b",
    )
    abstract = jax.eval_shape(model.init, jax.random.key(0))
    specs = model.specs()
    ospecs = optimizer_state_specs(
        specs, abstract, OptimizerConfig(zero_one_enabled=True)
    )

    is_p = lambda s: s is None or isinstance(s, P)  # noqa: E731
    trees = [
        ("model", abstract, specs, None),  # param dtype from eval_shape
        ("optim.master", abstract, ospecs.master, 4),
        ("optim.mu", abstract, ospecs.mu, 4),
        ("optim.nu", abstract, ospecs.nu, 4),
    ]

    per_proc = np.zeros(n_proc)
    replicated_bytes = 0.0
    tp_only_bytes = 0.0  # sharded leaves whose replica-0 chunks all sit on
    # the pp=0 slice (e.g. embeddings/head under P(..., "tp"))
    total_bytes = 0.0
    for kind, atree, stree, force_itemsize in trees:
        flat_a = jax.tree.leaves(atree)
        flat_s = jax.tree.leaves(stree, is_leaf=is_p)
        assert len(flat_a) == len(flat_s), (kind, len(flat_a), len(flat_s))
        for leaf, spec in zip(flat_a, flat_s):
            if leaf is None:
                continue
            itemsize = force_itemsize or leaf.dtype.itemsize
            sharding = NamedSharding(mesh, spec if spec is not None else P())
            owners = plan_chunk_writers(leaf.shape, sharding)
            leaf_procs = set()
            leaf_bytes = 0.0
            for norm, dev in owners.items():
                nbytes = itemsize * float(
                    np.prod([b - a for a, b in norm]) if norm else 1
                )
                proc = proc_of(dev)
                per_proc[proc] += nbytes
                leaf_procs.add(proc)
                leaf_bytes += nbytes
                total_bytes += nbytes
            if len(owners) == 1:
                replicated_bytes += leaf_bytes
            elif max(leaf_procs) < max(1, n_proc // pp):
                tp_only_bytes += leaf_bytes

    parallel_state.destroy_model_parallel()
    gb = 1 / 2**30
    return {
        "plan": f"{model_name}_ckpt_bytes",
        "mesh": {"tp": tp, "pp": pp},
        "devices_per_process": devices_per_process,
        "processes": n_proc,
        "total_bytes": int(total_bytes),
        "per_process_bytes": [int(b) for b in per_proc],
        "total_GB": round(total_bytes * gb, 2),
        "per_process_GB": [round(b * gb, 3) for b in per_proc],
        "max_GB": round(per_proc.max() * gb, 3),
        "min_GB": round(per_proc.min() * gb, 3),
        "mean_GB": round(per_proc.mean() * gb, 3),
        "imbalance_max_over_mean": round(
            float(per_proc.max() / per_proc.mean()), 2
        ),
        "replicated_GB_on_proc0": round(replicated_bytes * gb, 3),
        "tp_only_GB_on_pp0_procs": round(tp_only_bytes * gb, 3),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--devices-per-process", type=int, default=4)
    ap.add_argument("--model", default="llama3-70b")
    args = ap.parse_args()

    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

    set_cpu_devices(TP * PP)

    print(
        json.dumps(compute_plan(args.devices_per_process, args.model)),
        flush=True,
    )


if __name__ == "__main__":
    main()
