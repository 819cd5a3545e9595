"""The serving check on a decode model with a fault planted in how it treats a
state-space layer's state, its convolution tail, its inner norms or its
attention layers' positions — variants the check has to fail, beside the sound
readings of ``check_calibrate.py`` — or on an engine built with other
``PagedConfig`` fields (a state pool in the precision below the one the
configuration states). One process, one engine; the reader compares ``ok``,
``all_rows``, ``decode_rows_p50`` and ``cache`` with a sound run's.

    chiprun -- python3 benchmarks/tools/check_ssm_variant.py jamba-smallchat-bursty --seed 0 --set cache_dtype=bfloat16
    ... --fault no_carry      (a later chunk starts from the zero state: the carried state is lost)
    ... --fault no_tail       (a later chunk's convolution starts from zeros: the carried tail is lost)
    ... --fault no_reset      (a first chunk continues from whatever its slot held)
    ... --fault rotary        (a rotary table, theta 10,000, applied in the attention layers)
    ... --fault no_b_norm     (B goes into the scan as projected: one inner norm left out)
    ... --fault no_dt_norm    (dt_proj reads its input as projected)
"""

import argparse
import itertools
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402


def _lose(what: str):
    """``MambaMixer.mix`` that starts a block of several rows from a zero
    ``h`` (``what`` 0) or a zero tail (1) whatever it was handed."""
    from neuronx_distributed_llama3_2_tpu.models.jamba import MambaMixer

    sound = MambaMixer.mix

    def mix(self, params, u, g, h, tail, live, **how):
        if u.shape[1] > 1:
            h, tail = (h * 0, tail) if what == "h" else (h, tail * 0)
        return sound(self, params, u, g, h, tail, live, **how)

    return [(MambaMixer, "mix", mix)]


def _no_reset():
    from neuronx_distributed_llama3_2_tpu.inference.model import JambaDecode

    sound = JambaDecode._carried
    return [(JambaDecode, "_carried",
             lambda self, h, tail, at, fresh, unfold: sound(self, h, tail, at, False, unfold))]


def _rotary():
    from neuronx_distributed_llama3_2_tpu.models.llama import LlamaAttention, precompute_rope

    sound = LlamaAttention._apply_rope

    def rope(self, q, k, sin, cos, positions):
        if sin is None:
            sin, cos = precompute_rope(self.config.head_dim, 4096, 10000.0)
        return sound(self, q, k, sin, cos, positions)

    return [(LlamaAttention, "_apply_rope", rope)]


def _without(norm: str):
    """``_rms`` that hands its input on unnormed at every call that is
    ``norm``'s: ``ssm_params`` calls it once a norm, in ``INNER_NORMS``' order."""
    from neuronx_distributed_llama3_2_tpu.models import jamba

    sound, calls = jamba._rms, itertools.count()

    def rms(x, scale, eps):
        mine = jamba.INNER_NORMS[next(calls) % len(jamba.INNER_NORMS)] == norm
        return x if mine else sound(x, scale, eps)

    return [(jamba, "_rms", rms)]


# fault -> the (owner, attribute, replacement) triples that plant it
FAULTS = {
    "no_carry": lambda: _lose("h"),
    "no_tail": lambda: _lose("tail"),
    "no_reset": _no_reset,
    "rotary": _rotary,
    "no_b_norm": lambda: _without("b_norm"),
    "no_dt_norm": lambda: _without("dt_norm"),
}


def plant(fault: str) -> None:
    for owner, name, value in FAULTS[fault]():
        setattr(owner, name, value)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--set", action="append", default=[], metavar="FIELD=VALUE",
                    help="a PagedConfig field of the variant; *_dtype values name a jax.numpy dtype")
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None,
                    help="planted in the program before the engine is built")
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
