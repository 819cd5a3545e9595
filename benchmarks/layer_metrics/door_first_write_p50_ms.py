"""The engine's ``first_token`` mark -> the first SSE chunk written and
drained (end of ``door.first_write``): the rest of the engine step, the pump,
the handler's turn of the loop, the socket; median."""
from benchmarks import program_trace, stats


def read(r):
    if r["kind"] != "serving":
        return None
    legs = program_trace.ttft_legs(r)
    return stats.median(legs["first_write"]) if legs else None
