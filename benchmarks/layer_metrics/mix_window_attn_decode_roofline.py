"""Cache bytes the traced ``pdecode`` calls *need* from the window layers
(the dispatch records' ``window_rows`` — min(context, window) a live lane —
once a window layer) over the device time under ``attn/window`` in
``pdecode``, over the chip's memory bandwidth. The gather moves every lane's
whole ring (window - 1 + the top prefill rung, in blocks)."""
from benchmarks import window_trace


def read(r):
    return window_trace.kind_decode_roofline(r, "window")
