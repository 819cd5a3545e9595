"""The same reading as ``decode_batch_occupancy``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.decode_batch_occupancy import read  # noqa: F401
