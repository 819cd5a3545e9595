"""Client side: from the instant a request was due to its first SSE token;
median over the requests due in the window (a fixed set on a pinned
schedule)."""
from benchmarks import serving, stats


def read(r):
    return stats.median(serving.ttft_ms(r["in_window"])) if r["kind"] == "serving" else None
