"""Counts of a MiniCPM-SALA stack's two mixers from shapes, kept with the
benchmark (``arith.py``'s conventions). The program's code is
``models/minicpm_sala.py`` and ``inference/model.py`` ``SalaDecode``.

*A sparse layer's decode step* (``attn/sparse`` in ``pdecode``) needs, a live
lane a layer: the pooled keys of the kernels its context has completed — one
row of ``kv_heads · head_dim`` values every ``stride`` rows — read once to
score them, and the k and v rows of the blocks it takes — at most ``topk`` —
read once, each kv group its own half of a row. What the program moves on top
of that is not counted: both groups' halves of every gathered row, the pooled
keys up to the kv rung for idle lanes too, a kernel's rows read back to pool
them.

*A Lightning layer's prefill chunk* (``attn/lightning/chunk`` in ``pctx`` /
``psfx``) needs, a real row a head: ``q·kᵀ`` against the rows of the chunk at
or before it and the weighted sum of their ``v`` (the causal half of two ``t ×
t × d`` products), ``q·S`` into the carried state and ``kᵀ·v`` out to it (``d ×
d`` each): ``2·d·(t + 1) + 4·d²`` multiply-adds' worth of FLOPs a row at ``t``
real rows a chunk. The masked upper half of the two square products, padding
rows and the decay's exponentials are not counted.

One rule keeps a share built from these under 100 %: every count is of the
work the step *needs*, over a device time in which the program did at least
that."""

from __future__ import annotations

ROW_ITEMSIZE = 2        # k, v and the pooled keys in bfloat16


def selected_blocks(context: int, block: int, topk: int) -> int:
    """Blocks a decode query at the last of ``context`` rows takes."""
    return min((context - 1) // block + 1, topk)


def sparse_decode_needed_bytes(context: float, layers: int, kv_heads: int, head_dim: int,
                               stride: int, kernel: int, block: int, topk: int) -> float:
    """Bytes one live lane's decode step needs under ``attn/sparse``: the
    complete kernels' pooled keys once, the taken blocks' k and v rows once
    (the query's own block up to its row)."""
    row = kv_heads * head_dim * ROW_ITEMSIZE
    kernels = max((context - kernel) // stride + 1, 0)
    taken = selected_blocks(int(context), block, topk)
    rows = (taken - 1) * block + (int(context) - 1) % block + 1
    return float(layers) * (kernels * row + 2 * rows * row)


def lightning_chunk_flops(real_rows: float, calls: int, layers: int, heads: int, head_dim: int) -> float:
    """FLOPs the traced prefill calls need under ``attn/lightning/chunk``:
    ``real_rows`` rows in all over ``calls`` chunks a layer."""
    if not calls:
        return 0.0
    t = real_rows / calls
    per_row = 2.0 * head_dim * (t + 1.0) + 4.0 * head_dim * head_dim
    return float(layers) * heads * real_rows * per_row
