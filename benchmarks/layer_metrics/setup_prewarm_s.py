"""Seconds in ``setup.prewarm``: the loop over the catalog's keys. The five
costliest ``setup.program`` spans go to the notes.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    value = setup_trace.span_seconds(r, "setup.prewarm")
    if value is not None:
        r.setdefault("notes", []).append(
            "setup.prewarm, costliest programs: " + "; ".join(setup_trace.costliest_programs(r))
        )
    return value
