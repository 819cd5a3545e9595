"""The state-space step kernel on the chip, alone: against ``selective_step``
and bit for bit on every slot no live lane names (the compiled Mosaic call:
the CPU tests see the interpreter), then its time a layer at several live
counts beside the plain pass over every slot it replaces. One JSON line.

    chiprun -- python3 scripts/ssm_step_bench.py                 # Jamba2-3B's widths, 128 lanes, 26 layers
    python3 scripts/ssm_step_bench.py --rehearse-on-cpu 1 --lanes 8 --layers 2 --d-inner 128 --d-state 8
"""

import argparse
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=128)
    ap.add_argument("--layers", type=int, default=26)
    ap.add_argument("--d-inner", type=int, default=5120)
    ap.add_argument("--d-state", type=int, default=16)
    ap.add_argument("--live", type=int, nargs="*", default=None, help="live lanes of each timed case")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rehearse-on-cpu", type=int, default=0)
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        os.environ["NXDT_KERNEL_MODE"] = "interpret"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.utils.runtime import require_tpu, set_cpu_devices

    if args.rehearse_on_cpu:
        set_cpu_devices(args.rehearse_on_cpu)
        device = {"platform": "cpu"}
    else:
        device = require_tpu()
    # importing Pallas for the TPU starts the backend: after the platform is chosen
    from neuronx_distributed_llama3_2_tpu.kernels.ssm_step_pallas import ssm_step_paged, visits
    from neuronx_distributed_llama3_2_tpu.models.jamba import selective_step

    b, nl, d, n = args.lanes, args.layers, args.d_inner, args.d_state
    slots = b + 1
    keys = jax.random.split(jax.random.key(0), 5)
    delta = jax.nn.softplus(jax.random.normal(keys[1], (b, d)) - 3.0)
    c = jax.random.normal(keys[2], (b, d)).astype(jnp.bfloat16)
    b_t, c_t = jax.random.normal(keys[3], (b, n)), jax.random.normal(keys[4], (b, n))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, d))
    d_skip = jnp.ones((d,), jnp.float32)
    step = (delta, c, b_t, c_t, a, d_skip)

    def index_of(live, rng):
        """``live`` lanes, scattered over the batch, each on a slot of its own; the rest on the null slot."""
        index = np.zeros((b,), np.int32)
        index[rng.permutation(b)[:live]] = 1 + rng.permutation(b)[:live]
        return jnp.asarray(index)

    def kernel_layers(pool, index):
        live = index != 0
        lane, count = visits(live)
        slot = index[lane]

        def layer(flat, j):
            y, flat = ssm_step_paged(flat, j * slots + slot, lane, count, live, *step)
            return flat, y

        flat, y = jax.lax.scan(layer, pool.reshape((-1, n, d)), jnp.arange(nl, dtype=jnp.int32))
        return flat.reshape(pool.shape), y

    def pass_layers(pool, index):
        """What ``JambaDecode`` runs off the kernel: every slot of a layer where it lies."""
        named = jnp.zeros((slots,), bool).at[index].set(True)
        by_slot = [jnp.zeros((slots,) + x.shape[1:], x.dtype).at[index].set(x) for x in (delta, c, b_t, c_t)]
        by_slot[0] = jnp.where(named[:, None], by_slot[0], 0.0)

        def layer(pool, j):
            h = jax.lax.dynamic_index_in_dim(pool, j, 0, keepdims=False)
            y, h = selective_step(h, *by_slot, a, d_skip)
            return jax.lax.dynamic_update_index_in_dim(pool, h, j, 0), y[index]

        return jax.lax.scan(layer, pool, jnp.arange(nl, dtype=jnp.int32))

    fresh = jax.jit(lambda: jax.random.normal(keys[0], (nl, slots, n, d), jnp.float32))
    kernel, plain = (jax.jit(f, donate_argnums=0) for f in (kernel_layers, pass_layers))
    rng = np.random.default_rng(0)
    out = {"device": device, "lanes": b, "layers": nl, "d_inner": d, "d_state": n, "check": {}, "ms_a_layer": {}}
    for live in sorted({0, 1, b // 2, b}):
        index = index_of(live, rng)
        before = np.asarray(fresh())
        pool, y = kernel(fresh(), index)
        pool, y, at = np.asarray(pool), np.asarray(y), np.asarray(index)
        alive = at != 0
        want_y, want_h = jax.jit(jax.vmap(lambda h: selective_step(h, *step)))(jnp.asarray(before[:, at]))
        named = np.zeros((slots,), bool)
        named[at[alive]] = True
        out["check"][str(live)] = {
            "y_err": float(np.abs(y - np.asarray(want_y))[:, alive].max(initial=0.0)),
            "h_err": float(np.abs(pool[:, at] - np.asarray(want_h))[:, alive].max(initial=0.0)),
            "other_slots_bit_for_bit": bool((pool[:, ~named] == before[:, ~named]).all()),
            "idle_y_zero": bool((y[:, ~alive] == 0).all()),
        }
    for live in (args.live if args.live is not None else sorted({1, 22 * b // 128, 55 * b // 128, b})):
        index = index_of(live, rng)
        row = {}
        for name, fn in (("kernel", kernel), ("pass", plain)):
            pool, y = fn(fresh(), index)
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            for _ in range(args.steps):
                pool, y = fn(pool, index)
            jax.block_until_ready(y)
            row[name] = 1e3 * (time.perf_counter() - t0) / args.steps / nl
            del pool
        out["ms_a_layer"][str(live)] = row
    print("SSM_STEP_BENCH: " + json.dumps(out))
    ok = all(v["other_slots_bit_for_bit"] and v["idle_y_zero"] and max(v["y_err"], v["h_err"]) < 1e-4
             for v in out["check"].values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
