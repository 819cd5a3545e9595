"""The same reading as ``prefill_dev_tokens_per_s``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.prefill_dev_tokens_per_s import read  # noqa: F401
