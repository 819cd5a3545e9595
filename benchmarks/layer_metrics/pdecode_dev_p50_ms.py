"""Median device duration of the module runs whose ``program_id`` carries
the scope ``pdecode`` — the same quantity as ``decode_step_dev_ms``, found by
an exact join instead of pairing in order."""
from benchmarks import program_trace, stats


def read(r):
    if r["kind"] != "serving":
        return None
    runs = program_trace.program_run_ms(r, "pdecode")
    return stats.median(runs) if runs else None
