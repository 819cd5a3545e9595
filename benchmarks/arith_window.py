"""Byte counts of a stack that mixes window and full attention layers, from
shapes, kept with the benchmark (``arith.py``'s conventions). The program's
code is ``inference/model.py`` ``LagunaDecode``: a full layer reads the lane's
rows through the block table, a window layer the lane's ring.

One rule keeps a roofline share built from these under 100 %: a share counts
the bytes a decode step *needs* — each live lane's visible rows once a layer
(its whole context in a full layer, ``min(context, window)`` in a window
layer: the dispatch records' ``rows`` and ``window_rows``), every weight
once — while the gather *moves* every lane's whole top rung in a full layer
and every lane's whole ring in a window layer, at least as many, and
attention reads them again. The time cannot be shorter than the needed bytes
at the peak.
"""

from __future__ import annotations

from typing import Sequence

from benchmarks import arith_moe


def row_bytes(kv_heads: int, head_dim: int, itemsize: int = 2) -> int:
    """Bytes of one token's cache row in one layer as counted: K and V."""
    return 2 * kv_heads * head_dim * itemsize


def decode_needed_row_bytes(rows: float, layers: int, kv_heads: int, head_dim: int,
                            itemsize: int = 2) -> float:
    """Cache bytes a decode step has to read from one kind: ``rows`` (the rows
    the live lanes see in a layer of that kind, summed over lanes) once in each
    of the kind's ``layers``."""
    return float(rows) * layers * row_bytes(kv_heads, head_dim, itemsize)


def attention_weight_bytes(hidden: int, heads: int, kv_heads: int, head_dim: int,
                           itemsize: int = 2) -> float:
    """q, k, v, o and the per-head output gate of one layer."""
    return float(itemsize) * hidden * (2 * heads * head_dim + 2 * kv_heads * head_dim + heads)


def decode_weight_bytes(hidden: int, heads_per_layer: Sequence[int], kv_heads: int, head_dim: int,
                        mlp_layer_types: Sequence[str], dense_width: int, num_experts: int,
                        expert_width: int, shared_width: int, vocab: int,
                        itemsize: int = 2) -> float:
    """Weight bytes one decode step reads: every layer's attention, its dense
    SwiGLU or its router, every expert (32 lanes x 8 choices reach all 256)
    and the shared expert, the two norms; the final norm and the head. The
    embedding is read a row a lane and is not counted."""
    total = 0.0
    for heads, mlp in zip(heads_per_layer, mlp_layer_types):
        total += attention_weight_bytes(hidden, heads, kv_heads, head_dim, itemsize)
        total += 2 * hidden * itemsize
        if mlp == "dense":
            total += arith_moe.expert_weight_bytes(hidden, dense_width, itemsize)
        else:
            total += hidden * num_experts * itemsize
            total += num_experts * arith_moe.expert_weight_bytes(hidden, expert_width, itemsize)
            total += arith_moe.expert_weight_bytes(hidden, shared_width, itemsize)
    return total + (hidden + hidden * vocab) * itemsize


def held_share(rows: float, lanes: float, full_layers: int, full_row_bytes: int,
               window_layers: int, window_row_bytes: int, ring_rows: int) -> float:
    """Cache bytes the live lanes hold — their contexts' rows (``rows``, summed
    over ``lanes`` lanes) in the full layers and one whole ring a lane in the
    window layers — over the bytes the same contexts would hold were every
    layer full. Over 1 for lanes shorter than the ring pays for."""
    held = rows * full_layers * full_row_bytes + lanes * ring_rows * window_layers * window_row_bytes
    every = rows * (full_layers * full_row_bytes + window_layers * window_row_bytes)
    return held / every
