"""The same reading as ``kv_dev_share``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.kv_dev_share import read  # noqa: F401
