"""The same reading as ``prefill_dev_tokens_per_s``, under this cell's own name
because a per-layer metric names the one end-to-end metric it moves; 0 where
the traced segment holds no prefill call (``mla_trace.no_prefill_in_segment``)."""
from benchmarks.layer_metrics.qa_prefill_dev_tokens_per_s import read  # noqa: F401
