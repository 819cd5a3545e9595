"""Median device duration of one ``pdecode`` execution."""
from benchmarks import serving_trace, stats


def read(r):
    if r["kind"] != "serving":
        return None
    kinds, why = serving_trace.classify(r)
    if kinds is None:
        r.setdefault("notes", []).append(f"decode_step_dev_ms omitted: {why}")
        return None
    return stats.median([d * 1e3 for d, _ in kinds["decode"]])
