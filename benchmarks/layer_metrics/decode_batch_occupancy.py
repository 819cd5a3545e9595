"""Live lanes over lanes, averaged over the decode dispatches the engine's
flight recorder saw while the profiler ran."""
from benchmarks import serving_trace


def read(r):
    prof = r.get("profile") or {}
    lanes = [
        int(args["lanes"])
        for kind, args in serving_trace.engine_dispatches(prof.get("engine_steps", []))
        if kind == "decode" and "lanes" in args
    ]
    if not lanes:
        return None
    return 100.0 * sum(lanes) / len(lanes) / int(r["cell"].traffic["engine"]["lanes"])
