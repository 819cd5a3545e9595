"""Cache bytes the traced ``pdecode`` calls *need*
(``arith_mla.decode_needed_latent_bytes``: the live lanes' rows — the dispatch
records' ``rows`` — once a layer, as counted) over the device time under
``attn`` in ``pdecode``, over the chip's memory bandwidth."""
import statistics

import numpy as np

from benchmarks import arith_mla, mla_trace, moe_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, rows = r["model_cfg"], mla_trace.decode_rows(r)
    if rows is None or not hasattr(c, "kv_lora_rank"):
        return None
    calls = moe_trace.program_calls(r, ("pdecode",))
    seconds = mla_trace.seconds_in(r, ("attn",), ("pdecode",))
    if not calls or not seconds:
        return None
    need = calls * arith_mla.decode_needed_latent_bytes(
        statistics.fmean(rows), c.num_layers, c.kv_lora_rank, c.qk_rope_head_dim,
        itemsize=np.dtype(c.dtype).itemsize)
    r.setdefault("notes", []).append(
        f"latent attention in decode: {calls} calls over {statistics.fmean(rows):.0f} live rows need "
        f"{need / 1e9:.2f} GB of cache rows, {seconds:.3f} s under attn")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
