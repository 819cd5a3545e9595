"""Seconds in ``setup.runtime``: the backend's initialisation, the first
``jax.devices()`` of the process.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    return setup_trace.span_seconds(r, "setup.runtime")
