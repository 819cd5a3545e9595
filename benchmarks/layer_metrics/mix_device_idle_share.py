"""The same reading as ``device_idle_share``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.device_idle_share import read  # noqa: F401
