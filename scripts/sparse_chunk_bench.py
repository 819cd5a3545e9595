"""The sparse layers' chunk-read kernel on the chip, alone: the compiled Mosaic
call against ``attend_tiles`` on the same bfloat16 operands and both against a
float32 evaluation (the CPU tests see the interpreter), then a sparse layer's
whole attention (``SalaDecode._attend_sparse``: the write, the pooled keys,
the selection, the read) a call at several rungs with the kernel and with the
tile walk it replaces, over a pool the size of ``sala-longctx-steady``'s and a
permuted table, and the kernel path's pieces alone (the call, the rung's
gather, the selection). One JSON line.

    chiprun -- python3 scripts/sparse_chunk_bench.py             # MiniCPM-SALA's widths, 512 rows
    python3 scripts/sparse_chunk_bench.py --rehearse-on-cpu 1 --preset tiny-sala --rows 16 --rungs 64 128 \
        --pool-blocks 80 --reps 2
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="minicpm-sala")
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--rungs", type=int, nargs="*", default=[2048, 8192, 20480, 33280])
    ap.add_argument("--pool-blocks", type=int, default=12512)
    ap.add_argument("--reps", type=int, default=16, help="layers chained in one program")
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="QUERY_TILE x KV_TILE x STRIP_ROWS variants to time, e.g. 256x512x16")
    ap.add_argument("--rehearse-on-cpu", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.utils.runtime import require_tpu, set_cpu_devices

    if args.rehearse_on_cpu:
        set_cpu_devices(args.rehearse_on_cpu)
        device, modes = {"platform": "cpu"}, {"kernel": "interpret", "tiles": "reference"}
    else:
        device, modes = require_tpu(), {"kernel": "compiled", "tiles": "reference"}
    # importing Pallas for the TPU starts the backend: after the platform is chosen
    from neuronx_distributed_llama3_2_tpu.inference.model import SalaDecode
    from neuronx_distributed_llama3_2_tpu.kernels import sparse_chunk_pallas as kernel_module
    from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (
        SALA_CONFIGS, SPARSE, attend_tiles, block_mask, select_blocks,
    )

    # the cell's two sparse layers; the pool has no other
    c = dataclasses.replace(SALA_CONFIGS[args.preset], num_layers=2, mixer_types=(SPARSE, SPARSE))
    model = SalaDecode(c)
    t, bs, n, nkv, d = args.rows, c.sparse_block_size, c.num_heads, c.num_kv_heads, c.head_dim
    dtype = jnp.float32 if args.rehearse_on_cpu else jnp.bfloat16
    top = max(args.rungs)
    rng = np.random.default_rng(0)
    table = jnp.asarray(1 + rng.permutation(args.pool_blocks - 1)[:top // bs + t // bs][None], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 6)
    q, k, v = (jax.random.normal(key, (1, t, heads, d), jnp.float32).astype(dtype)
               for key, heads in zip(keys[:3], (n, nkv, nkv)))
    live = jnp.full((1,), t, jnp.int32)

    def fresh():
        return tuple(
            (0.5 * jax.random.normal(key, a.shape, jnp.float32)).astype(dtype)
            for key, a in zip(keys[3:], model.init_paged_cache(args.pool_blocks, bs, dtype, state_blocks=2).rows))

    def program(limit, reps):
        """``reps`` sparse layers' attention chained (a layer's output is the next one's q)."""
        def run(rows, start):
            pos = start + jnp.arange(t, dtype=jnp.int32)[None]

            def layer(i, carry):
                x, rows = carry
                att, rows = model._attend_sparse(x, k, v, rows, i % 2, pos, live, table, limit)
                return (att * 4.0).astype(dtype), rows

            return jax.lax.fori_loop(0, reps, layer, (q, rows))

        return jax.jit(run, donate_argnums=0)

    def timed(fn, rows, start):
        out, rows = fn(rows, start)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out, rows = fn(rows, start)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0), rows

    out = {"device": device, "preset": args.preset, "rows": t, "check": {}, "ms_a_layer": {}, "ms_a_layer_by_tile": {}}
    # -- the compiled call against its twin and a float32 evaluation, on arrays --
    limit = min(args.rungs)
    kk, vv = (jax.random.normal(key, (1, nkv, limit, d), jnp.float32) for key in keys[3:5])
    start = jnp.asarray([limit - t], jnp.int32)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)
    own = (pos // bs)[:, :, None, None]
    at = jnp.arange(limit // bs)
    mask = (jax.random.bernoulli(keys[5], 0.5, (1, t, nkv, limit // bs)) | (at == own)) & (at <= own)

    def twin(q, kk, vv):
        whole = lambda i: (jnp.swapaxes(kk, 1, 2), jnp.swapaxes(vv, 1, 2))  # noqa: E731
        return attend_tiles(q, pos, mask, whole, 1, limit // bs, bs)

    os.environ["NXDT_KERNEL_MODE"] = modes["kernel"]
    got = kernel_module.sparse_chunk_attend(q, kk.astype(dtype), vv.astype(dtype), mask, start, bs).astype(jnp.float32)
    low = jax.jit(twin)(q, kk.astype(dtype), vv.astype(dtype)).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(twin)(q.astype(jnp.float32), kk.astype(dtype).astype(jnp.float32),
                              vv.astype(dtype).astype(jnp.float32))
    scale = float(jnp.abs(exact).max())
    out["check"] = {
        "rung": limit, "kernel_vs_tiles": float(jnp.abs(got - low).max()) / scale,
        "kernel_vs_float32": float(jnp.abs(got - exact).max()) / scale,
        "tiles_vs_float32": float(jnp.abs(low - exact).max()) / scale,
    }
    del kk, vv, got, low, exact

    # -- a sparse layer's whole attention a call, kernel and tile walk --
    rows = fresh()
    for limit in args.rungs:
        row = {}
        for name, mode in modes.items():
            os.environ["NXDT_KERNEL_MODE"] = mode
            ms, rows = timed(program(limit, args.reps), rows, jnp.int32(limit - t))
            row[name] = ms / args.reps
        out["ms_a_layer"][str(limit)] = row
    os.environ["NXDT_KERNEL_MODE"] = modes["kernel"]
    # -- the pieces of the kernel's path alone, a layer: the call itself, the
    #    rung's gather, the selection (scores, top-k) --
    k_blocks, v_blocks = (a.reshape(-1, bs, d) for a in rows[:2])
    heads = jnp.arange(nkv, dtype=jnp.int32)
    for limit in args.rungs:
        blocks = limit // bs
        pos = (limit - t) + jnp.arange(t, dtype=jnp.int32)[None]
        at = (table[:, :blocks])[:, None, :] * nkv + heads[:, None]
        pooled = jax.random.normal(keys[5], (1, blocks * c.kernels_per_block, nkv, d), jnp.float32).astype(dtype)
        chosen, taken = jax.jit(lambda x: select_blocks(x, pooled, pos, c))(q)
        mask = block_mask(chosen, taken, blocks)
        k_rung, v_rung = (pool[at].reshape(1, nkv, limit, d) for pool in (k_blocks, v_blocks))

        def chained(piece, *operands):
            # the operands go in as arguments: a closure over a pool-sized array bakes it into the executable
            fn = jax.jit(lambda x, *operands: jax.lax.fori_loop(0, args.reps, lambda i, x: piece(x, *operands), x))
            jax.block_until_ready(fn(q, *operands))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, *operands))
            return 1e3 * (time.perf_counter() - t0) / args.reps

        def gather(x, pool):
            got = pool[at + (x[0, 0, 0, 0] > 1e9)].reshape(1, nkv, limit, d)
            return x + got[:, 0, :t, None, :] * 1e-9

        def select(x, pooled):
            chosen, taken = select_blocks(x, pooled, pos, c)
            return x + (chosen[:, :, :1, :1] * taken[:, :, :1, :1]).astype(x.dtype) * 1e-9

        def call(x, k_rung, v_rung, mask):
            return 4.0 * kernel_module.sparse_chunk_attend(x, k_rung, v_rung, mask, pos[:, 0], bs)

        out["ms_a_layer"][str(limit)].update({
            "call_alone": chained(call, k_rung, v_rung, mask),
            "gather_alone": chained(gather, k_blocks), "select_alone": chained(select, pooled),
        })
    for variant in args.tiles:
        kernel_module.QUERY_TILE, kernel_module.KV_TILE = (
            int(x) for x in variant.split("x"))
        kernel_module._chunk_attend.clear_cache()
        row = {}
        for limit in args.rungs:
            if model._chunk_kernel_takes(t, limit):
                ms, rows = timed(program(limit, args.reps), rows, jnp.int32(limit - t))
                row[str(limit)] = ms / args.reps
        out["ms_a_layer_by_tile"][variant] = row
    print("SPARSE_CHUNK_BENCH: " + json.dumps(out))
    check = out["check"]
    return 0 if check["kernel_vs_float32"] < 2 * max(check["tiles_vs_float32"], 1e-5) else 1


if __name__ == "__main__":
    sys.exit(main())
