"""Same samples as ``tpot_p50_ms``, 90th percentile."""
from benchmarks import serving, stats


def read(r):
    return stats.percentile(serving.tpot_ms(r["in_window"]), 90.0) if r["kind"] == "serving" else None
