"""The serving check on a decode model with a fault planted in how its window
layers mask — a variant the check has to fail, beside the sound readings of
``check_calibrate.py`` and its ``--kv int8``. One process, one engine.

    chiprun -- python3 benchmarks/tools/check_window_variant.py laguna-mixedlen-batch --seed 0 --fault no_lower_bound
    ... --fault no_lower_bound    (a window layer attends to every earlier row its table holds)
    ... --fault one_key_short     (the window is 511 keys, not 512)
"""

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402

# what a window layer is told its window is, given the configuration's
FAULTS = {"no_lower_bound": lambda w: None, "one_key_short": lambda w: w - 1}


def plant(fault: str) -> None:
    from neuronx_distributed_llama3_2_tpu.models.laguna import LagunaAttention

    sound = LagunaAttention.window

    def window(self):
        w = sound(self)
        return w if w is None else FAULTS[fault](w)

    LagunaAttention.window = window


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True,
                    help="planted in LagunaAttention.window before the engine is built")
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
    plant(args.fault)
    _, _, checked = serving.build(
        cell, spec.load_family(cell.config["family"]), args.seed, rehearsal, False, {})
    print(f"seed {args.seed} fault {args.fault}: {json.dumps(checked)}", flush=True)


if __name__ == "__main__":
    main()
