"""Operation and byte counts computed from shapes — the benchmark's own copy
(the program's ``flops.py`` counts attention without causal halving).

Conventions, stated once:

- A matmul of (m, k) by (k, n) is ``2*m*k*n`` FLOPs.
- Training utilization counts the operations the forward and backward passes
  *require*: ``6 * matmul parameters`` per token (forward 2, backward 4) plus
  causal attention, whose score and value matmuls touch half the square.
  Recomputed operations (remat, the flash backward's second ``QK^T``) do not
  count toward ``train_mfu``; they do count for a *kernel's* roofline share,
  because that asks how fast the kernel did what it was called to do.
- Embedding lookups are gathers, not matmuls: the table is left out of the
  matmul parameters; the LM head is in.
"""

from __future__ import annotations

from typing import Dict, Tuple


NOT_MATMUL = ("embed", "bias", "norm", "scale")


def matmul_params(shapes: Dict[str, Tuple[int, ...]]) -> int:
    """Parameters that sit in matmuls, from ``{path: shape}``: every leaf of
    two or more dimensions whose path names no embedding table, bias or norm
    (stacked over layers, those are two-dimensional too)."""
    total = 0
    for path, shape in shapes.items():
        if len(shape) < 2 or any(k in path for k in NOT_MATMUL):
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n
    return total


def train_flops_per_token(
    n_matmul_params: int, num_layers: int, num_heads: int, head_dim: int,
    seq_len: int,
) -> float:
    """Required training FLOPs per token: ``6*N`` plus causal attention.
    Attention forward per token per layer is ``QK^T`` and ``PV``, each
    ``2 * S * heads * head_dim`` over the full square, halved by the causal
    mask; backward is twice the forward."""
    attn_fwd = 2 * (2 * seq_len * num_heads * head_dim) / 2.0
    return 6.0 * n_matmul_params + 3.0 * attn_fwd * num_layers


def mfu(tokens_per_s: float, flops_per_token: float, chips: int, peak_flops: float) -> float:
    return tokens_per_s * flops_per_token / (chips * peak_flops)


# matmuls of (S x D) by (D x S) shape each flash kernel performs per call:
# forward QK^T, PV; the dq kernel recomputes QK^T, forms dP = dO V^T and
# dQ = dS K; the dkv kernel recomputes QK^T, forms dP, dV = P^T dO, dK = dS^T Q
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# (batch, heads, seq, head_dim)-sized arrays each kernel reads and writes
FLASH_ARRAYS = {
    "flash_fwd": 4,      # q k v -> o
    "flash_bwd_dq": 6,   # q k v o do -> dq
    "flash_bwd_dkv": 7,  # q k v o do -> dk dv
}


def flash_call_cost(
    kernel: str, batch: int, heads: int, kv_heads: int, seq: int,
    head_dim: int, itemsize: int = 2, causal: bool = True,
) -> Tuple[float, float]:
    """(FLOPs, bytes) one call of a flash-attention kernel needs. K and V
    (and dK, dV) have ``kv_heads`` heads; everything else has ``heads``."""
    per_matmul = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        per_matmul /= 2.0
    flops = FLASH_MATMULS[kernel] * per_matmul
    q_like = batch * heads * seq * head_dim * itemsize
    kv_like = batch * kv_heads * seq * head_dim * itemsize
    n = FLASH_ARRAYS[kernel]
    kv_arrays = 2 if kernel != "flash_bwd_dkv" else 4
    return flops, float((n - kv_arrays) * q_like + kv_arrays * kv_like)


def roofline_seconds(flops: float, nbytes: float, peak_flops: float, peak_bytes_per_s: float) -> Tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
