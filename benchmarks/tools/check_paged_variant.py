"""The serving check on an engine built with other ``PagedConfig`` fields than
the cell's — a variant the check has to fail, beside the sound readings of
``check_calibrate.py`` (which offers ``--kv`` alone): a state pool in the
precision below the one the configuration states — or on a decode model with
a planted fault in how it treats a state (``--fault``), to say which faults
the check sees at the cell's size and which it does not. One process, one
engine.

    chiprun -- python3 benchmarks/tools/check_paged_variant.py brumby-longgen-batch --seed 0 --set cache_dtype=bfloat16
    ... --fault no_carry     (a later chunk starts from the zero state: the carried state is lost)
    ... --fault no_reset     (a first chunk continues from whatever its block held)
"""

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402


# what ``forward`` is told about where a block of rows starts, whatever its caller said
FAULTS = {"no_carry": True, "no_reset": False}


def plant(fault: str) -> None:
    from neuronx_distributed_llama3_2_tpu.inference.model import RetentionDecode

    sound = RetentionDecode.forward

    def forward(self, params, cache, tokens, *args, context_encode=False, **kw):
        if tokens.shape[1] > 1:
            context_encode = FAULTS[fault]
        return sound(self, params, cache, tokens, *args, context_encode=context_encode, **kw)

    RetentionDecode.forward = forward


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--set", action="append", default=[], metavar="FIELD=VALUE",
                    help="a PagedConfig field of the variant; *_dtype values name a jax.numpy dtype")
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None,
                    help="a fault planted in RetentionDecode.forward before the engine is built")
    ap.add_argument("--rehearse-on-cpu", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache, require_tpu, set_cpu_devices,
    )

    fields = {}
    for item in args.set:
        key, value = item.split("=", 1)
        fields[key] = getattr(jnp, value) if key == "cache_dtype" else json.loads(value)
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
    if args.fault:
        plant(args.fault)
    _, _, checked = serving.build(
        cell, spec.load_family(cell.config["family"]), args.seed, rehearsal, False, {},
        calibrate=fields,
    )
    print(f"seed {args.seed} variant {args.set or args.fault}: {json.dumps(checked)}", flush=True)


if __name__ == "__main__":
    main()
