"""Seconds in ``setup.cost_profiles``: graftmeter's harvest at the end of
prewarm. It asks every program's record for its lowering again and jit's own
lowering cache answers (one ``lower`` event a program a start), so it reads
about 0.01 s (``PERF.md`` §5 **Set-up**).
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    return setup_trace.span_seconds(r, "setup.cost_profiles")
