"""Seconds in ``backend_compile`` during set-up (a cache load is inside it),
``setup.facts`` left out.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    return setup_trace.compile_s(r)
