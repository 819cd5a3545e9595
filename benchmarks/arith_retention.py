"""Operation and byte counts of power retention from shapes, kept with the
benchmark (``arith.py``'s conventions: a matmul of (m, k) by (k, n) is
``2*m*k*n`` FLOPs). The program's code is ``models/brumby.py``: a layer's
memory of a sequence is a state ``S`` (kv heads, φ, head) and a normaliser
``z`` (kv heads, φ) in float32; ``pdecode`` reads and rewrites one state a
live lane a layer (``attn/retention/step``), a prefill chunk reads the state
for every query row and adds the chunk's keys to it (``attn/retention/chunk``)
after expanding q and k by φ (``attn/retention/expand``).

Two rules keep a roofline share built from these under 100 %:

- the bandwidth shares count the bytes a decode step *needs* at the
  **narrowest** φ the mathematics allows — the exact symmetric square,
  ``d·(d+1)/2`` = 8,256 at d = 128 — read once and written once, whatever the
  program's φ is (a wider one moves more bytes in the same time and reads
  lower, as it should);
- the compute share counts the matmul work the traced chunks *execute* at the
  program's own φ (every row of the bucket, live or padding) over the device
  time under the two scopes that ran it.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4      # float32, as the configuration states
CHUNK_ROWS = 128        # the program's (models/brumby.py RETENTION_CHUNK); a test holds them equal


def narrowest_feature_width(head_dim: int) -> int:
    """The exact symmetric square: ``a_i a_j`` for ``i <= j``."""
    return head_dim * (head_dim + 1) // 2


def state_bytes(kv_heads: int, head_dim: int, width: int, itemsize: int = STATE_ITEMSIZE) -> int:
    """Bytes of one sequence's state in one layer at a φ of ``width``."""
    return kv_heads * width * (head_dim + 1) * itemsize


def decode_needed_state_bytes(lanes: float, layers: int, kv_heads: int, head_dim: int) -> float:
    """State bytes a decode step over ``lanes`` live lanes has to move: each
    lane's state read once and written once in every layer, narrowest φ."""
    return float(lanes) * layers * 2 * state_bytes(
        kv_heads, head_dim, narrowest_feature_width(head_dim))


def decode_weight_bytes(hidden: int, heads: int, kv_heads: int, head_dim: int,
                        intermediate: int, vocab: int, layers: int, itemsize: int = 2) -> float:
    """Weight bytes a decode step reads once for all lanes: every layer's
    projections, gate and SwiGLU, and the untied head (the embedding table is
    a gather of one row a lane)."""
    layer = hidden * heads * head_dim * 2 + hidden * kv_heads * head_dim * 2 \
        + hidden * kv_heads + 3 * hidden * intermediate
    return float(itemsize) * (layers * layer + hidden * vocab)


def chunk_retention_flops(bucket: int, heads: int, kv_heads: int, head_dim: int,
                          width: int) -> float:
    """Matmul FLOPs one prefill call executes under ``attn/retention/chunk``
    a layer, over ``bucket`` rows taken ``CHUNK_ROWS`` at a time: the in-chunk
    weights and values, the carried state's read (numerator and normaliser)
    and the state's update. φ itself and the decays are elementwise and left
    out, which keeps the share on the low side."""
    rows = min(bucket, CHUNK_ROWS)
    chunks = -(-bucket // CHUNK_ROWS)
    within = 2.0 * rows * rows * heads * head_dim * 2             # scores, values
    carried = 2.0 * rows * heads * width * (head_dim + 1)         # φ(q)ᵀ S, φ(q)·z
    update = 2.0 * rows * kv_heads * width * head_dim             # φ(k)ᵀ v
    return chunks * (within + carried + update)
