"""The engine's own ``request_info()["queue_ms"]`` (submit -> first
admission), as the final payload of each request carries it; median."""
from benchmarks import stats


def read(r):
    waits = [s.queue_ms for s in r.get("in_window", []) if s.queue_ms is not None]
    return stats.median(waits)
