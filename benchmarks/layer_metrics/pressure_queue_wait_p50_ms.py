"""The same reading as ``queue_wait_p50_ms``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.queue_wait_p50_ms import read  # noqa: F401
