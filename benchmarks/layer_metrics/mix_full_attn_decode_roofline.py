"""Cache bytes the traced ``pdecode`` calls *need* from the full layers
(``arith_window.decode_needed_row_bytes``: the live lanes' contexts — the
dispatch records' ``rows`` — once a full layer, K and V as counted) over the
device time under ``attn/full`` in ``pdecode``, over the chip's memory
bandwidth. The gather moves every lane's whole kv rung, not its live rows:
what this share is low by."""
from benchmarks import window_trace


def read(r):
    return window_trace.kind_decode_roofline(r, "full")
