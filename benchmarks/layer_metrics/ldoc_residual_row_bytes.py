"""Bytes a token's streams take between layers (the tracer's ``setup``
record: ``residual_row_bytes``; 4 streams x 3,584 x 2 bytes = 28,672 as
published) — what every sub-layer reads and writes a token beside its own
work, the analogue of ``qa_cache_row_bytes``."""
from benchmarks import program_trace


def read(r):
    tl = program_trace.timeline(r) if r.get("kind") == "serving" else None
    value = (tl or {}).get("setup", {}).get("residual_row_bytes")
    return float(value) if value else None
