"""Bytes one ``pdecode`` *needs* — every layer's weights and the head once
for all lanes, and the live lanes' visible rows at their row bytes (the
dispatch records' ``rows`` in the full layers, ``window_rows`` in the window
layers: ``arith_window``) — over the mean device time of the traced
``pdecode`` runs, over the chip's memory bandwidth: the share of the whole
step, which bounds every later claim on this cell's decode."""
import statistics

from benchmarks import arith_window, window_trace


def read(r):
    if r.get("peaks") is None or not window_trace.names_kinds():
        return None
    got = window_trace.decode_calls(r)
    if got is None:
        return None
    mean = window_trace.mean_decode_record(r)
    if not got[0] or mean is None:
        return 0.0 if window_trace.decode_records(r) is not None else None
    c, item = r["model_cfg"], window_trace._itemsize(r["model_cfg"])
    weights = arith_window.decode_weight_bytes(
        c.hidden_size, c.num_heads_per_layer, c.num_kv_heads, c.head_dim, c.mlp_layer_types,
        c.intermediate_size, c.num_experts, c.moe_intermediate_size,
        c.shared_expert_intermediate_size, c.vocab_size, itemsize=item)
    rows = sum(
        arith_window.decode_needed_row_bytes(n, c.layers_of(kind), c.num_kv_heads, c.head_dim, item)
        for kind, n in (("full", mean[1]), ("window", mean[2])))
    seconds = statistics.fmean(got[1]) / 1e3
    r.setdefault("notes", []).append(
        f"a decode step needs {weights / 1e9:.2f} GB of weights + {rows / 1e9:.3f} GB of cache rows "
        f"({mean[0]:.1f} live lanes, {mean[1]:.0f} full-kind rows, {mean[2]:.0f} window-kind rows), "
        f"runs {seconds * 1e3:.2f} ms")
    return 100.0 * (weights + rows) / seconds / r["peaks"].hbm_bytes_per_s
