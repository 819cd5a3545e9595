"""Bytes one ``pdecode`` *needs* — every weight once for all lanes
(``arith_ssm.decode_weight_bytes``), the live lanes' states read and written,
and the attention layers' live rows (the dispatch records' ``rows``) at a
row's bytes — over the mean device time of the traced ``pdecode`` runs, over
the chip's memory bandwidth: the share of the whole step, which bounds every
later claim on this cell's decode."""
import statistics

import numpy as np

from benchmarks import arith_ssm, program_trace, ssm_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, records = r["model_cfg"], ssm_trace.decode_records(r)
    runs = program_trace.program_run_ms(r, "pdecode")
    if records is None or not runs:
        return None
    itemsize = np.dtype(c.dtype).itemsize
    mamba, attention = c.layers_of("mamba"), c.layers_of("attention")
    weights = arith_ssm.decode_weight_bytes(
        c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim, c.intermediate_size,
        c.vocab_size, mamba, attention, c.d_inner, c.mamba_d_state, c.mamba_d_conv,
        c.mamba_dt_rank, itemsize=itemsize)
    lanes = statistics.fmean(live for _, live, _ in records)
    states = arith_ssm.decode_needed_state_bytes(
        lanes, mamba, c.d_inner, c.mamba_d_state, c.mamba_d_conv)
    rows = statistics.fmean(n for n, _, _ in records) \
        * attention * 2 * c.num_kv_heads * c.head_dim * itemsize
    seconds = statistics.fmean(runs) / 1e3
    r.setdefault("notes", []).append(
        f"a decode step needs {weights / 1e9:.2f} GB of weights + {states / 1e9:.3f} GB of states "
        f"({lanes:.1f} live lanes) + {rows / 1e9:.3f} GB of rows, runs {seconds * 1e3:.2f} ms")
    return 100.0 * (weights + states + rows) / seconds / r["peaks"].hbm_bytes_per_s
