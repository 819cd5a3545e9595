"""Bytes of one sequence's state over all layers as the device lays the pool
out (the tracer's ``setup`` record: ``state_bytes_per_lane``, one block of the
pool). The configuration file states 38,043,648 a layer at the program's φ."""
from benchmarks import retention_trace


def read(r):
    value = retention_trace.state_bytes_per_lane(r)
    return float(value) if value else None
