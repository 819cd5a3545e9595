"""The same reading as ``moe_dev_share``, under this cell's own name because a
per-layer metric lists the cells that report it."""
from benchmarks.layer_metrics.moe_dev_share import read  # noqa: F401
