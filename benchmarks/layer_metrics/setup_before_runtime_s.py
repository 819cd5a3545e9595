"""Seconds from the process's start to the opening of ``setup.runtime``: the
interpreter, ``import jax``, the package's and the benchmark's imports.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    return setup_trace.before_runtime_s(r)
