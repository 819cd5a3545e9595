"""The same reading as ``rag_expert_load_cv``, under this cell's own name because a
per-layer metric names the one end-to-end metric it moves."""
from benchmarks.layer_metrics.rag_expert_load_cv import read  # noqa: F401
