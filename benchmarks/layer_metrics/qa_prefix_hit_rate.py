"""The same reading as ``prefix_hit_rate``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.prefix_hit_rate import read  # noqa: F401
