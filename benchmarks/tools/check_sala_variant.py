"""The serving check on a decode model with a fault planted in how a
MiniCPM-SALA stack treats its selection, its pooled keys, its Lightning state
or its sparse layers' positions — variants the check has to fail, beside the
sound readings of ``check_calibrate.py`` — or on an engine built with other
``PagedConfig`` fields (a state pool in the precision below the one the
configuration states). One process, one engine; the reader compares ``ok``,
``all_rows``, ``decode_rows_p50`` and ``cache`` with a sound run's.

    chiprun -- python3 benchmarks/tools/check_sala_variant.py sala-longctx-steady --seed 0 --set cache_dtype=bfloat16
    ... --fault no_selection  (the selection ignored: every row at or before the query is read)
    ... --fault unpooled      (top-k taken from un-pooled scores: a kernel's key is its first row, not the mean of its rows)
    ... --fault no_decay      (lambda = 1 in every head: the Lightning state never forgets)
    ... --fault rotary        (a rotary table, theta 10,000, applied in the minicpm4 layers)
    ... --fault no_carry      (a later chunk's Lightning layers start from the zero state)
    ... --fault no_window     (the window's blocks are not forced: top-k by score alone after the first block)

``FAULTS`` are what ``tests/test_minicpm_sala_serving.py`` plants on the CPU,
where every row is held to the reference; the traffic file's ``check_doc`` says
which of them the chip's check sees."""

import argparse
import dataclasses
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import serving, spec  # noqa: E402


def _no_selection():
    """``select_blocks`` that names every block, taken where causal."""
    import jax.numpy as jnp

    from neuronx_distributed_llama3_2_tpu.models import minicpm_sala as sala

    def every(q, pooled, q_pos, config):
        b, t = q_pos.shape
        nkv = pooled.shape[2]
        blocks = pooled.shape[1] // config.kernels_per_block
        chosen = jnp.broadcast_to(jnp.arange(blocks, dtype=jnp.int32), (b, t, nkv, blocks))
        return chosen, chosen <= (q_pos // config.sparse_block_size)[..., None, None]

    return [(sala, "select_blocks", every)]


def _unpooled():
    from neuronx_distributed_llama3_2_tpu.models import minicpm_sala as sala

    return [(sala, "pool_keys", lambda rows: rows[..., 0, :])]


def _no_decay():
    import jax.numpy as jnp

    from neuronx_distributed_llama3_2_tpu.models import minicpm_sala as sala

    return [(sala, "lightning_slopes", lambda heads: jnp.zeros((heads,), jnp.float32))]


def _rotary():
    from neuronx_distributed_llama3_2_tpu.models import minicpm_sala as sala
    from neuronx_distributed_llama3_2_tpu.models.llama import apply_rope, precompute_rope

    sound = sala.SalaMixer.project

    def project(self, params, x, sin, cos, positions):
        q, k, v = sound(self, params, x, sin, cos, positions)
        if self.kind == sala.SPARSE:
            sin, cos = precompute_rope(self.config.head_dim, 40960, 10000.0)
            q, k = apply_rope(q, sin, cos, positions), apply_rope(k, sin, cos, positions)
        return q, k, v

    return [(sala.SalaMixer, "project", project)]


def _no_carry():
    from neuronx_distributed_llama3_2_tpu.models import minicpm_sala as sala

    sound = sala.lightning_chunk
    return [(sala, "lightning_chunk", lambda q, k, v, s, live, slopes: sound(q, k, v, s * 0, live, slopes))]


def _no_window():
    from neuronx_distributed_llama3_2_tpu.models import minicpm_sala as sala

    sound = sala.select_blocks
    return [(sala, "select_blocks", lambda q, pooled, q_pos, config: sound(
        q, pooled, q_pos, dataclasses.replace(config, sparse_window=1)))]


# fault -> the (owner, attribute, replacement) triples that plant it
FAULTS = {
    "no_selection": _no_selection,
    "unpooled": _unpooled,
    "no_decay": _no_decay,
    "rotary": _rotary,
    "no_carry": _no_carry,
    "no_window": _no_window,
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
