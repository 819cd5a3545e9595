"""Time to first token in a closed-loop batch cell (queue time included): a
layer metric there, because the callers wait for whole replies. The same
reading as ``ttft_p50_ms``."""
from benchmarks import serving, stats


def read(r):
    return stats.median(serving.ttft_ms(r["in_window"])) if r["kind"] == "serving" else None
