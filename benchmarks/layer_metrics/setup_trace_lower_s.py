"""Seconds JAX spent tracing (the union of the trace events' intervals) and
lowering during set-up, ``setup.facts`` left out: what a warm compile cache
cannot save.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    return setup_trace.trace_lower_s(r)
