"""Per request (last token - first token) / (tokens - 1), client side; median
over the completed requests that were due in the window."""
from benchmarks import serving, stats


def read(r):
    return stats.median(serving.tpot_ms(r["in_window"])) if r["kind"] == "serving" else None
