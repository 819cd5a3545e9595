"""Calibrate the serving check on the chip: how far a correct run sits from the
float32 reference over several seeds, and how far a variant that has to fail
(an 8-bit KV pool) sits — the numbers ``check.py``'s tolerances are argued
from. One process, one engine: run it once per seed and variant.

    chiprun -- python3 benchmarks/tools/check_calibrate.py <workload> --seed 0 [--kv int8]
"""

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv", default=None, help="PagedConfig.kv_cache_dtype of the variant")
    ap.add_argument("--rehearse-on-cpu", type=int, default=0)
    args = ap.parse_args()

    import jax
    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache, require_tpu, set_cpu_devices,
    )

    cell = spec.load_cell(args.workload)
    rehearsal = args.rehearse_on_cpu > 0
    if rehearsal:
        os.environ["NXDT_KERNEL_MODE"] = "interpret"
        set_cpu_devices(args.rehearse_on_cpu)
        cell = cell.for_rehearsal()
    else:
        require_tpu()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    enable_compile_cache()
    split = {}
    _, _, checked = serving.build(
        cell, spec.load_family(cell.config["family"]), args.seed, rehearsal, False, split,
        calibrate={"kv_cache_dtype": args.kv} if args.kv else None,
    )
    print(f"seed {args.seed} kv {args.kv or 'default'} set-up {json.dumps(split)}: "
          f"{json.dumps(checked)}", flush=True)


if __name__ == "__main__":
    main()
