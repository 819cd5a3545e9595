"""Bytes one ``pdecode`` *needs* — every layer's weights and the head once
for all lanes, and the live lanes' states read and written at the narrowest φ
(``arith_retention``) — over the device time of the traced ``pdecode`` runs,
over the chip's memory bandwidth: the share of the whole step, which bounds
every later claim on this cell's decode."""
import statistics

import numpy as np

from benchmarks import arith_retention, program_trace, retention_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, lanes = r["model_cfg"], retention_trace.live_lanes(r)
    runs = program_trace.program_run_ms(r, "pdecode")
    if lanes is None or not runs:
        return None
    weights = arith_retention.decode_weight_bytes(
        c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim, c.intermediate_size,
        c.vocab_size, c.num_layers, itemsize=np.dtype(c.dtype).itemsize)
    states = arith_retention.decode_needed_state_bytes(
        statistics.fmean(lanes), c.num_layers, c.num_kv_heads, c.head_dim)
    seconds = statistics.fmean(runs) / 1e3
    r.setdefault("notes", []).append(
        f"a decode step needs {weights / 1e9:.2f} GB of weights + {states / 1e9:.2f} GB of states "
        f"({statistics.fmean(lanes):.1f} live lanes), runs {seconds * 1e3:.2f} ms")
    return 100.0 * (weights + states) / seconds / r["peaks"].hbm_bytes_per_s
