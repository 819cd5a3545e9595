"""The same reading as ``step_host_self_ms``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.step_host_self_ms import read  # noqa: F401
