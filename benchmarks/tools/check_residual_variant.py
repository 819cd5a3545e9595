"""The serving check on a Xing4.0 decode model with one fact of the model
planted wrong — a variant the check has to fail, beside the sound readings of
``check_calibrate.py``. The fault is planted in the program's functions before
the engine is built (no switch in the program); the reference stays true. One
process, one engine.

    chiprun -- python3 benchmarks/tools/check_residual_variant.py xing-longdoc-batch --seed 0 --fault one_sinkhorn_round
    ... --fault one_sinkhorn_round   (H_res after 1 round of row / column normalisation, not 20)
    ... --fault identity_res         (H_res = I: the streams are never mixed)
    ... --fault no_yarn              (plain rotary tables; the softmax scale keeps its YaRN factor)
    ... --fault q_latent_no_norm     (the query latent goes un-normed into W_UQ)

A latent pool in the precision below bfloat16 is ``check_paged_variant.py
<cell> --set cache_dtype=float8_e4m3fn`` (part C has to fail it)."""

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402

FAULTS = ("one_sinkhorn_round", "identity_res", "no_yarn", "q_latent_no_norm")


def plant(fault: str):
    """Plant ``fault`` in the program's model code; returns the function that
    takes it out again (the tests plant and un-plant in one process)."""
    import jax.numpy as jnp
    from neuronx_distributed_llama3_2_tpu.models import sarvam, xing

    if fault in ("one_sinkhorn_round", "identity_res"):
        sound = xing.sinkhorn
        if fault == "one_sinkhorn_round":
            xing.sinkhorn = lambda m, rounds, eps: sound(m, 1, eps)
        else:
            xing.sinkhorn = lambda m, rounds, eps: jnp.broadcast_to(
                jnp.eye(m.shape[-1], dtype=m.dtype), m.shape)
        return lambda: setattr(xing, "sinkhorn", sound)
    if fault == "no_yarn":
        sound = sarvam.yarn_rope
        sarvam.yarn_rope = lambda rotary_dim, max_seq_len, theta, yarn: sound(
            rotary_dim, max_seq_len, theta, None)
        return lambda: setattr(sarvam, "yarn_rope", sound)
    if fault == "q_latent_no_norm":
        sound = sarvam.RMSNorm
        query_ranks = {c.q_lora_rank for c in xing.XING_CONFIGS.values()}

        def norm(dim, *args, **kwargs):
            if dim in query_ranks:       # the key-value latent's widths are other numbers
                return lambda params, x: x
            return sound(dim, *args, **kwargs)

        sarvam.RMSNorm = norm
        return lambda: setattr(sarvam, "RMSNorm", sound)
    raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault", choices=FAULTS, required=True,
                    help="planted in models/xing.py / models/sarvam.py before the engine is built")
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
