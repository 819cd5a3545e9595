"""Self time of an engine step: the step record's duration less the part its
``dispatch`` / ``prefill`` / ``prefill_chunk`` / ``readback`` children cover;
median over the steps of the measured window (tracer on, profiler off)."""
from benchmarks import program_trace, stats


def read(r):
    if r["kind"] != "serving":
        return None
    tl = program_trace.timeline(r)
    if tl is None:
        return None
    lo, hi = r["window"]
    return stats.median(
        program_trace.step_self_ms(s) for s in tl["steps"] if lo <= s["t0"] and s["t1"] <= hi
    )
