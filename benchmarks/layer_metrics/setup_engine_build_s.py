"""Seconds in ``setup.inference_engine`` + ``setup.paged_engine`` less
``setup.prewarm``, ``setup.cost_profiles`` and ``setup.facts``: placement,
pool, residents, catalog, the freeze.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    return setup_trace.engine_build_s(r)
