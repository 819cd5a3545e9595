"""Operation and byte counts of latent attention (MLA) from shapes, kept with
the benchmark (``arith.py``'s conventions: a matmul of (m, k) by (k, n) is
``2*m*k*n`` FLOPs). The program's code is ``models/sarvam.py``
``latent_attention``: *expanded* — the rows attended are up-projected to keys
and values by head (``attn/latent_up``), then scores and values by head
(``attn/sdpa``) — or *absorbed* — multi-query attention over the rows
themselves (``attn/sdpa``), the up-projections folded into the query and the
output (``attn/absorb``).

Two rules keep a roofline share built from these under 100 %:

- the compute share counts the work the traced calls *execute* under
  ``attn/latent_up`` and ``attn/sdpa`` — every query row of the bucket against
  every row gathered, masked or not — over the device time under those two
  scopes, so the time cannot have done less;
- the bandwidth share counts the cache bytes a decode step *needs*: the live
  lanes' rows once a layer, as counted (576 values of 2 bytes). The gather
  moves every lane's whole ``kv_limit`` rows, at least as many, and attention
  reads them again.
"""

from __future__ import annotations

QUERY_BLOCK = 512     # the program's (models/sarvam.py); a test holds them equal


def absorbed_is_cheaper(t: int, rank: int, d_nope: int, d_rope: int, d_v: int) -> bool:
    """The program's rule (``models/sarvam.py``; a test holds the two equal):
    ``t`` queries over cached rows run absorbed where that is fewer FLOPs."""
    return 2 * rank + d_rope < d_nope + d_rope + d_v + rank * (d_nope + d_v) / t


def executed_queries(t: int) -> int:
    """Query rows a call computes: past one block, whole blocks."""
    return t if t <= QUERY_BLOCK else -(-t // QUERY_BLOCK) * QUERY_BLOCK


def prefill_attention_flops(bucket: int, kv_limit: int, heads: int, rank: int,
                            d_nope: int, d_rope: int, d_v: int) -> float:
    """FLOPs one prefill call executes under ``attn/latent_up`` and
    ``attn/sdpa``, a layer. ``kv_limit`` 0 is ``pctx``: the fresh block
    against itself, expanded; otherwise ``psfx`` over ``kv_limit`` gathered
    rows in the form the program's rule picks."""
    rows = kv_limit or bucket
    queries = executed_queries(bucket)
    if kv_limit and absorbed_is_cheaper(bucket, rank, d_nope, d_rope, d_v):
        return 2.0 * queries * rows * heads * ((rank + d_rope) + rank)
    up = 2.0 * rows * rank * heads * (d_nope + d_v)
    return up + 2.0 * queries * rows * heads * ((d_nope + d_rope) + d_v)


def latent_row_bytes(rank: int, d_rope: int, itemsize: int = 2) -> int:
    """Bytes of one cache row as counted: ``[c ‖ k_r]``."""
    return (rank + d_rope) * itemsize


def decode_needed_latent_bytes(rows: float, layers: int, rank: int, d_rope: int,
                               itemsize: int = 2) -> float:
    """Cache bytes a decode step over ``rows`` live rows (all lanes) has to
    read: each once in every layer."""
    return float(rows) * layers * latent_row_bytes(rank, d_rope, itemsize)
