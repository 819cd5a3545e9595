"""Bytes the traced ``pdecode`` calls *need* under ``attn/sparse``
(``arith_sala.sparse_decode_needed_bytes``: a live lane's complete pooled keys
scored once and the k / v rows of the blocks it takes read once, a sparse
layer — from the dispatch records' ``sparse_rows_cached`` and live lanes) over
the device time under ``attn/sparse`` in ``pdecode`` (pooling the fresh
kernel, the selection and the read), over the chip's memory bandwidth. The
program gathers both kv groups' halves of every taken row and scores idle
lanes' null tables too, so it reads low; a kernel that walks a kv group's
chosen blocks where they lie raises it."""
import statistics

from benchmarks import arith_sala, sala_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, records = r["model_cfg"], sala_trace.decode_records(r)
    if records is None:
        return None
    sala_trace.add_to_breakdown(r)
    calls = sala_trace.program_calls(r, ("pdecode",))
    seconds = sala_trace.seconds_in(r, sala_trace.SPARSE, ("pdecode",))
    if not calls or not seconds:
        return None
    lanes = statistics.fmean(live for live, _, _, _ in records)
    cached = statistics.fmean(rows for _, rows, _, _ in records)
    context = cached / max(lanes, 1e-9)         # rows a live lane holds, the mean
    need = calls * lanes * arith_sala.sparse_decode_needed_bytes(
        context, c.layers_of("minicpm4"), c.num_kv_heads, c.head_dim, c.kernel_stride,
        c.kernel_size, c.sparse_block_size, c.sparse_topk)
    read_rows = statistics.fmean(rows for _, _, rows, _ in records) / max(lanes, 1e-9)
    r.setdefault("notes", []).append(
        f"sparse in decode: {calls} calls over {lanes:.1f} live lanes of {context:.0f} cached rows "
        f"read {read_rows:.0f} rows a lane a layer and need {need / 1e9:.3f} GB, "
        f"{seconds:.3f} s under attn/sparse")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
