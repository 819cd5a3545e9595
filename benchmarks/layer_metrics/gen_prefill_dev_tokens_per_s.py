"""The same reading as ``prefill_dev_tokens_per_s``, under this cell's own name
because a per-layer metric names the one end-to-end metric it moves; 0 where
the traced segment holds no prefill call (``retention_trace.no_prefill_in_segment``)."""
from benchmarks import retention_trace
from benchmarks.layer_metrics import prefill_dev_tokens_per_s


def read(r):
    value = prefill_dev_tokens_per_s.read(r)
    if value is None and r.get("kind") == "serving" and retention_trace.no_prefill_in_segment(r):
        return 0.0
    return value
