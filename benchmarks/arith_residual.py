"""Byte counts of a decode step of the Xing4.0 stack — latent attention with
a query latent under a multi-stream residual (``models/xing.py``) — from
shapes, kept with the benchmark (``arith.py``'s conventions; ``arith_mla.py``
and ``arith_moe.py`` have the cache rows' and the experts' own counts).

The rule that keeps ``ldoc_pdecode_roofline`` under 100 %: it counts the bytes
one ``pdecode`` *needs* — every weight the step multiplies by once for all
lanes (each routable expert once, ``arith_moe.decode_needed_weight_bytes``;
the head whole; of the embedding a row a lane, left out), and the live lanes'
latent rows once a layer as counted (``arith_mla.decode_needed_latent_bytes``)
— over a device time that moved at least as many: the gather moves every
lane's whole rung at the pool's padded row, attention reads it again, and the
streams between sub-layers are read and written beside them."""

from __future__ import annotations

from benchmarks import arith_moe


def residual_row_bytes(streams: int, hidden: int, itemsize: int = 2) -> int:
    """Bytes a token's streams take between layers (the analogue of a cache
    row's bytes): ``hc_mult`` rows of the hidden size."""
    return streams * hidden * itemsize


def connection_params(streams: int, hidden: int) -> int:
    """Parameters of one sub-layer's hyper-connection that a step reads:
    ``Φ`` (nC, 2n + n²); the gates and biases are a few dozen numbers."""
    return streams * hidden * (2 * streams + streams * streams)


def attention_params(hidden: int, heads: int, q_rank: int, kv_rank: int,
                     d_nope: int, d_rope: int, d_v: int) -> int:
    """``W_DQ``, ``W_UQ``, ``W_DKV``, ``W_UKV``, ``W_O`` of one layer."""
    return (hidden * q_rank + q_rank * heads * (d_nope + d_rope)
            + hidden * (kv_rank + d_rope) + kv_rank * heads * (d_nope + d_v)
            + heads * d_v * hidden)


def decode_weight_bytes(*, lanes: int, hidden: int, heads: int, q_rank: int, kv_rank: int,
                        d_nope: int, d_rope: int, d_v: int, streams: int,
                        dense_layers: int, dense_width: int,
                        expert_layers: int, num_experts: int, top_k: int, expert_width: int,
                        shared_width: int, vocab: int, itemsize: int = 2) -> float:
    """Weight bytes one ``pdecode`` over ``lanes`` lanes has to read."""
    layers = dense_layers + expert_layers
    every_layer = attention_params(hidden, heads, q_rank, kv_rank, d_nope, d_rope, d_v) \
        + 2 * connection_params(streams, hidden)
    dense = 3 * hidden * dense_width
    shared_and_router = 3 * hidden * shared_width + hidden * num_experts
    return (
        itemsize * (layers * every_layer + dense_layers * dense
                    + expert_layers * shared_and_router + hidden * vocab)
        + arith_moe.decode_needed_weight_bytes(
            lanes, top_k, num_experts, hidden, expert_width, expert_layers, itemsize)
    )
