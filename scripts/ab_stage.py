"""Single-chip A/B stages for the CPU-calibrated defaults (VERDICT r4 #5).

Two performance defaults were chosen on XLA:CPU cost-analysis evidence and
need on-chip timings before they count as banked:

- ``--which head``: ``PipelinedCausalLM.head_sequence_split=True`` replaces
  each lane's full-sequence LM-head/CE per 1F1B rotation with a 1/pp
  sequence slice (docs/head_waste.md). This stage times the per-lane
  per-rotation head compute both ways on the real chip — the fused chunked
  CE (the exact code the executor calls, parallel/loss.py
  fused_linear_cross_entropy) over (mbs, S, H) vs (mbs, S/pp, H). The two
  extra (mbs, S, H) psums of the split path ride ICI and cannot be timed
  on one chip; the record carries ``ici_unmeasured: true`` so the default
  stays provisional until a pod run, but the compute-side ratio — the
  dominant term — is captured on real hardware.

- ``--which ring``: zigzag vs contiguous causal ring attention
  (kernels/ring_attention_pallas.py). The multi-device rotation cannot run
  on one chip, but its critical path is a composition of pair kernels that
  can: per the executors' own decomposition, contiguous costs
  ``causal(C) + (cp-1)*full(C)`` on the worst lane (lane cp-1 computes a
  full past-chunk attention at every visit) while zigzag costs
  ``2*causal(C/2) + half(C/2) + (cp-1)*2*half(C/2)`` on every lane
  (each visit = exactly two balanced half-chunk kernels). This stage times
  the pair kinds on-chip and composes both critical paths — the
  rotation-timing A/B the defaults were waiting for. ppermute transfer
  time is layout-independent (same bytes either way) and excluded.

Prints ONE JSON line. ``--cpu --quick`` runs tiny shapes for plumbing
tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def time_fn(fn, *args, repeats=6):
    """Shared chained-scan timer (consumes every output leaf so sibling
    cotangents are never DCE'd) — utils/chipbench.py has the rationale."""
    from neuronx_distributed_llama3_2_tpu.utils.chipbench import (
        time_fn as _time_fn,
    )

    return _time_fn(fn, *args, repeats=repeats)


def head_ab(quick: bool, iters: int) -> dict:
    """Per-lane per-rotation head/CE cost: full sequence vs 1/pp slice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.parallel.loss import (
        fused_linear_cross_entropy,
    )

    if quick:
        H, V, S, pp, chunk = 128, 1024, 512, 4, 128
    else:
        # llama3-8b head geometry at the docs/head_waste.md pp=8 scenario
        H, V, S, pp, chunk = 4096, 128256, 8192, 8, 256
    mbs = 1
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.02, jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, (mbs, S)), jnp.int32)

    def loss(h, w, lab):
        s, _ = fused_linear_cross_entropy(
            h, lambda hc: hc @ w.astype(hc.dtype), lab, chunk_size=chunk
        )
        return s

    grad = jax.grad(loss, argnums=(0, 1))

    out = {}
    for name, s_lane in (("unsplit", S), ("split", S // pp)):
        h = jnp.asarray(rng.standard_normal((mbs, s_lane, H)) * 0.1, jnp.bfloat16)
        lab = labels[:, :s_lane]
        out[f"{name}_fwd_ms"] = round(
            time_fn(lambda h, w, lab: loss(h, w, lab), h, w, lab, repeats=iters)
            * 1e3,
            3,
        )
        out[f"{name}_fwdbwd_ms"] = round(
            time_fn(lambda h, w, lab: grad(h, w, lab), h, w, lab, repeats=iters)
            * 1e3,
            3,
        )
    out["compute_speedup_fwdbwd"] = round(
        out["unsplit_fwdbwd_ms"] / max(out["split_fwdbwd_ms"], 1e-9), 2
    )
    return {
        "ab": "head_sequence_split",
        "geometry": {"hidden": H, "vocab": V, "seq": S, "pp": pp, "mbs": mbs},
        # the split path's two (mbs, S, H) psums per rotation ride ICI and
        # are not measurable on one chip — the default stays provisional
        # for the ICI term; this record banks the compute term
        "ici_unmeasured": True,
        **out,
    }


def ring_ab(quick: bool, iters: int) -> dict:
    """Rotation critical path, contiguous vs zigzag, from pair timings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.kernels.pallas_flash_attention import (
        pallas_flash_attention,
    )

    B, N, NKV, D = 1, 32, 8, 64  # llama3.2-1b geometry
    cp = 4
    seqs = (1024,) if quick else (8192, 32768)

    def pair_ms(s_q, s_kv, causal):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, s_q, N, D)) * 0.1, jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, s_kv, NKV, D)) * 0.1, jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, s_kv, NKV, D)) * 0.1, jnp.bfloat16)

        def loss(q, k, v):
            # interpret mode engages automatically on CPU (plumbing tier)
            o = pallas_flash_attention(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))
        return (
            round(time_fn(lambda q, k, v: loss(q, k, v), q, k, v, repeats=iters) * 1e3, 3),
            round(time_fn(lambda q, k, v: g(q, k, v), q, k, v, repeats=iters) * 1e3, 3),
        )

    rows = []
    for S in seqs:
        C = S // cp
        full_f, full_fb = pair_ms(C, C, causal=False)
        causal_f, causal_fb = pair_ms(C, C, causal=True)
        half_f, half_fb = pair_ms(C // 2, C // 2, causal=False)
        chalf_f, chalf_fb = pair_ms(C // 2, C // 2, causal=True)
        row = {
            "seq": S,
            "cp": cp,
            "chunk": C,
            "pair_ms": {
                "full_fwdbwd": full_fb,
                "causal_fwdbwd": causal_fb,
                "half_fwdbwd": half_fb,
                "causal_half_fwdbwd": chalf_fb,
            },
        }
        for tag, (full, causal, half, chalf) in (
            ("fwd", (full_f, causal_f, half_f, chalf_f)),
            ("fwdbwd", (full_fb, causal_fb, half_fb, chalf_fb)),
        ):
            contig = causal + (cp - 1) * full
            zig = 2 * chalf + half + (cp - 1) * 2 * half
            row[f"critical_contiguous_{tag}_ms"] = round(contig, 3)
            row[f"critical_zigzag_{tag}_ms"] = round(zig, 3)
            row[f"zigzag_speedup_{tag}"] = round(contig / max(zig, 1e-9), 2)
        rows.append(row)
    return {
        "ab": "ring_zigzag_vs_contiguous",
        "geometry": {"batch": B, "heads": N, "kv_heads": NKV, "head_dim": D},
        "composition": {
            "contiguous": "causal(C) + (cp-1)*full(C)",
            "zigzag": "2*causal(C/2) + half(C/2) + (cp-1)*2*half(C/2)",
        },
        "rows": rows,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", required=True, choices=("head", "ring"))
    ap.add_argument("--cpu", action="store_true", help="CPU backend (plumbing)")
    ap.add_argument("--quick", action="store_true", help="tiny shapes")
    ap.add_argument("--iters", type=int, default=6)
    args = ap.parse_args()

    import jax

    if args.cpu:
        from neuronx_distributed_llama3_2_tpu.utils.runtime import set_cpu_devices

        set_cpu_devices(1)

    result = head_ab(args.quick, args.iters) if args.which == "head" else ring_ab(
        args.quick, args.iters
    )
    result["chip"] = str(jax.devices()[0])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
