"""The same reading as ``step_host_self_ms``, under this cell's own name because a
per-layer metric lists the cells that report it."""
from benchmarks.layer_metrics.step_host_self_ms import read  # noqa: F401
