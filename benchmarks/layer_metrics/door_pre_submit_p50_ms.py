"""Socket accepted in ``GraftServer._handle_http`` -> ``engine.submit``
returned (the ``request`` root's start to the end of its ``door.submit``
child), requests accepted in the window; median. The note sets the four
in-program legs of TTFT beside the client's."""
from benchmarks import program_trace, stats


def read(r):
    if r["kind"] != "serving":
        return None
    legs = program_trace.ttft_legs(r)
    return stats.median(legs["pre_submit"]) if legs else None
