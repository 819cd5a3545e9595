"""The same reading as ``moe_dev_share``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.moe_dev_share import read  # noqa: F401
