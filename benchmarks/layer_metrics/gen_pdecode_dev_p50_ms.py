"""The same reading as ``pdecode_dev_p50_ms``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.pdecode_dev_p50_ms import read  # noqa: F401
