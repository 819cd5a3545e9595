"""Device time of the ops under ``attn`` ... ``window`` — the attention block
of a window layer whole, its gather the lane's ring — over device busy time."""
from benchmarks import window_trace


def read(r):
    return window_trace.kind_share(r, "window")
