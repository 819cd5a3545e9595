"""Compile requests of set-up that the persistent cache did not hold,
``setup.facts`` left out: 0 or 1 says the line is a warm start.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    value = setup_trace.cache_misses(r)
    return None if value is None else float(value)
