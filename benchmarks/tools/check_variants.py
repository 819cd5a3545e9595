"""What the serving check reads on an engine that runs the cell's model with
one architecture fact wrong — the program is handed a changed model config,
the float32 reference and the check keep the true one, as a cell's
configuration file would. ``check.tolerance`` of a traffic file is argued from
these readings beside the sound ones of ``check_calibrate.py``. One process,
one engine: run it once per seed and variant.

    chiprun -- python3 benchmarks/tools/check_variants.py <workload> --seed 0 --set normalize_top_k=true
    ... --set top_k=7        (the last expert dropped)
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402


class Mistaken:
    """A family whose program config has ``wrong`` fields replaced; the
    reference's view is taken from the config before the change."""

    def __init__(self, family, wrong):
        self.family, self.wrong, self.reference = family, wrong, family.reference
        self.train_model = family.train_model

    def model_config(self, cfg, rehearsal, **overrides):
        self.true = self.family.model_config(cfg, rehearsal, **overrides)
        return dataclasses.replace(self.true, **self.wrong)

    def reference_config(self, model_cfg):
        return self.family.reference_config(self.true)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--set", action="append", default=[], metavar="FIELD=JSON",
                    help="a field of the program's model config and its wrong value")
    ap.add_argument("--rehearse-on-cpu", type=int, default=0)
    args = ap.parse_args()
    wrong = {k: json.loads(v) for k, v in (item.split("=", 1) for item in args.set)}

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
    _, _, checked = serving.build(
        cell, Mistaken(spec.load_family(cell.config["family"]), wrong), args.seed, rehearsal, False, {},
    )
    print(f"seed {args.seed} wrong {json.dumps(wrong)}: {json.dumps(checked)}", flush=True)


if __name__ == "__main__":
    main()
