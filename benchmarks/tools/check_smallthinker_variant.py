"""The serving check on a SmallThinker decode model with one of the facts
that no field of its model config holds planted wrong — a variant the check has
to fail, beside the sound readings of ``check_calibrate.py`` (and its ``--kv
int8``). One process, one engine.

    chiprun -- python3 benchmarks/tools/check_smallthinker_variant.py smallthinker-longchat-steady --seed 0 --fault router_after_attention
    ... --fault router_after_attention   (the experts routed from the post-attention state, like every other family)
    ... --fault silu_for_relu            (SwiGLU experts)
    ... --fault rotary_on_full           (the full layers rotate q and k too)
    ... --fault none_on_window           (no layer carries a position)

The faults a config field holds go through the tools that are there:
``check_variants.py <cell> --set normalize_top_k=false`` (gates not
renormalised), ``check_window_variant.py <cell> --fault no_lower_bound |
one_key_short`` (``SmallThinkerAttention`` inherits ``LagunaAttention.window``).
"""

import argparse
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402


def _router_after_attention():
    from neuronx_distributed_llama3_2_tpu.inference.model import SmallThinkerDecode

    SmallThinkerDecode._early_routes = lambda self, lp, h: None


def _silu_for_relu():
    import jax

    from neuronx_distributed_llama3_2_tpu.moe import experts

    experts.ACTIVATIONS["relu"] = jax.nn.silu


def _rotates(answer: bool):
    def plant():
        from neuronx_distributed_llama3_2_tpu.models.smallthinker import SmallThinkerConfig

        SmallThinkerConfig.rotates = lambda self, kind: answer
    return plant


FAULTS = {
    "router_after_attention": _router_after_attention,
    "silu_for_relu": _silu_for_relu,
    "rotary_on_full": _rotates(True),
    "none_on_window": _rotates(False),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True,
                    help="planted in the program before the engine is built; the reference stays as it is")
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
    FAULTS[args.fault]()
    _, _, checked = serving.build(
        cell, spec.load_family(cell.config["family"]), args.seed, rehearsal, False, {})
    print(f"seed {args.seed} fault {args.fault}: {json.dumps(checked)}", flush=True)


if __name__ == "__main__":
    main()
