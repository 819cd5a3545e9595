"""Same samples as ``ttft_p50_ms``, 90th percentile."""
from benchmarks import serving, stats


def read(r):
    return stats.percentile(serving.ttft_ms(r["in_window"]), 90.0) if r["kind"] == "serving" else None
