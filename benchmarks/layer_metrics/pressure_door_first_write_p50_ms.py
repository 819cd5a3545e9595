"""The same reading as ``door_first_write_p50_ms``, under this cell's own name because a
per-layer metric lists the cells that report it."""
from benchmarks.layer_metrics.door_first_write_p50_ms import read  # noqa: F401
