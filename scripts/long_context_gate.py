"""Long-context gate: 32K-token attention fwd+bwd on one real TPU chip.

The regression the reference runs on-device for long sequences
(test/integration/llama2_7B/test_long_seqlen.py:13, 32K through the NKI
kernel with its seq%2048 constraint, kernels/flash_attn.py:178). Here the
Pallas kernel has no alignment constraint; this gate runs 32K causal
fwd+bwd at Llama-3.2-1B head geometry and checks finiteness + throughput,
and (optionally, --cp) the same length through ring attention on a virtual
mesh for the multi-chip long-context path.

Usage:  python scripts/long_context_gate.py [--seq 32768] [--cp]
Prints one JSON line per gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def tpu_gate(
    seq: int, min_attn_util: float = 0.2, max_peak_gb: float = 14.0
) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.kernels.flash_attention import (
        flash_attention,
    )

    B, N, NKV, D = 1, 32, 8, 64  # llama3.2-1b geometry
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, seq, N, D)) * 0.1, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, seq, NKV, D)) * 0.1, jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, seq, NKV, D)) * 0.1, jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=1024, block_kv=1024)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    val, grads = fn(q, k, v)
    float(val)  # sync
    t0 = time.perf_counter()
    val, grads = fn(q, k, v)
    finite = bool(jnp.isfinite(val)) and all(
        bool(jnp.isfinite(g).all()) for g in grads
    )
    dt = time.perf_counter() - t0
    flops = 2 * 2 * B * N * seq * seq * D * 0.5 * 3.5  # fwd+bwd causal
    util = flops / dt / 197e12
    stats = jax.devices()[0].memory_stats() or {}
    peak_gb = stats.get("peak_bytes_in_use", 0) / 2**30
    # the reference's CI classification (test_long_seqlen.py:13-60:
    # SUCCEEDED / ERRORS / MEMORY_DEGRADATION / PERFORMANCE_DEGRADATION
    # against passed-in thresholds)
    if not finite:
        status = "ERRORS"
    elif peak_gb > max_peak_gb:
        status = "MEMORY_DEGRADATION"
    elif util < min_attn_util:
        status = "PERFORMANCE_DEGRADATION"
    else:
        status = "SUCCEEDED"
    print(
        json.dumps(
            {
                "gate": "long_context_tpu",
                "seq": seq,
                "status": status,
                "ok": status == "SUCCEEDED",
                "fwd_bwd_ms": round(dt * 1e3, 1),
                "attn_util": round(util, 3),
                "peak_hbm_gb": round(peak_gb, 2),
                "backend": jax.default_backend(),
            }
        )
    )
    if status != "SUCCEEDED":
        raise SystemExit(1)


def cp_gate(seq: int) -> None:
    """Same length through ring attention, cp=8 virtual mesh (CPU)."""
    import subprocess

    code = f"""
import jax
from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices
set_cpu_devices(8)
import json, time
import jax.numpy as jnp, numpy as np
from neuronx_distributed_llama3_2_tpu.kernels.ring_attention import ring_attention_sharded
from neuronx_distributed_llama3_2_tpu.parallel import state as ps

st = ps.initialize_model_parallel(context_parallel_size=8)
B, N, NKV, D = 1, 4, 2, 64
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((B, {seq}, N, D)) * 0.1, jnp.float32)
k = jnp.asarray(rng.standard_normal((B, {seq}, NKV, D)) * 0.1, jnp.float32)
v = jnp.asarray(rng.standard_normal((B, {seq}, NKV, D)) * 0.1, jnp.float32)
def loss(q, k, v):
    o = ring_attention_sharded(q, k, v, st.mesh, ps.CP_AXIS, causal=True)
    return jnp.sum(o.astype(jnp.float32) ** 2)
val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
finite = bool(jnp.isfinite(val)) and all(bool(jnp.isfinite(g).all()) for g in grads)
print(json.dumps({{"gate": "long_context_ring_cp8", "seq": {seq}, "ok": finite}}))
raise SystemExit(0 if finite else 1)
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    subprocess.run(
        [sys.executable, "-c", f"import sys; sys.path.insert(0, {repo!r})\n" + code],
        env=env, check=True, cwd=repo,
    )


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=32768)
    p.add_argument("--cp", action="store_true", help="also gate ring attention cp=8")
    p.add_argument(
        "--min-attn-util", type=float, default=0.2,
        help="below this attention MFU → PERFORMANCE_DEGRADATION",
    )
    p.add_argument(
        "--max-peak-gb", type=float, default=14.0,
        help="above this peak HBM → MEMORY_DEGRADATION",
    )
    args = p.parse_args()
    tpu_gate(args.seq, args.min_attn_util, args.max_peak_gb)
    if args.cp:
        cp_gate(args.seq)
