"""Set-up's seconds after ``setup.runtime`` under no span of the program: the
benchmark's weights from the seed, its correctness check, its imports. The
notes get ``setup.facts``' seconds (a traced engine's deep harvest, in no
metric): with them the six span metrics sum to ``setup_s``.
Read in ``--trace 1`` runs only: a traced start's value, not the judged
(untraced) ``setup_s``'s (``benchmarks/setup_trace.py``)."""
from benchmarks import setup_trace


def read(r):
    value = setup_trace.outside_spans_s(r)
    facts = setup_trace.span_seconds(r, "setup.facts")
    if value is not None and facts is not None:
        r.setdefault("notes", []).append(f"setup.facts {facts:.3f} s: traced engines only, in no metric")
    return value
