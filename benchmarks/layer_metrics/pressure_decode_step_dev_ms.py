"""The same reading as ``decode_step_dev_ms``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.decode_step_dev_ms import read  # noqa: F401
