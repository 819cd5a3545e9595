"""Bytes a token leaves in the pool, a layer, as the device lays the pool out
(the tracer's ``setup`` record: ``cache_row_bytes``, tile padding included).
As counted a latent row is 576 values of 2 bytes = 1,152."""
from benchmarks import program_trace


def read(r):
    tl = program_trace.timeline(r) if r.get("kind") == "serving" else None
    value = (tl or {}).get("setup", {}).get("cache_row_bytes")
    return float(value) if value else None
