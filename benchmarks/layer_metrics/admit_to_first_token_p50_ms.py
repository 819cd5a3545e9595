"""First admission (``prefilling`` starts) -> the engine commits the
request's first generated token (``first_token`` mark): the prefill chunks and
the engine steps between them; median."""
from benchmarks import program_trace, stats


def read(r):
    if r["kind"] != "serving":
        return None
    legs = program_trace.ttft_legs(r)
    return stats.median(legs["admit_to_first_token"]) if legs else None
