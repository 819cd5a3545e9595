"""The same reading as ``pdecode_dev_p50_ms``, under this cell's own name
because a per-layer metric names the one end-to-end metric it moves; 0 where
the traced segment holds no ``pdecode`` call."""
from benchmarks import stats, window_trace


def read(r):
    got = window_trace.decode_calls(r)
    if got is None:
        return None
    return stats.median(got[1]) if got[0] else 0.0
