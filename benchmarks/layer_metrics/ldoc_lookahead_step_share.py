"""The same reading as ``lookahead_step_share``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.lookahead_step_share import read  # noqa: F401
