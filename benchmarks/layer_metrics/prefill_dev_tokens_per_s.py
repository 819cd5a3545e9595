"""Prompt tokens prefilled over the device time of the ``pctx``/``psfx``
executions that prefilled them."""
from benchmarks import serving_trace


def read(r):
    if r["kind"] != "serving":
        return None
    kinds, why = serving_trace.classify(r)
    if kinds is None:
        r.setdefault("notes", []).append(f"prefill_dev_tokens_per_s omitted: {why}")
        return None
    seconds = sum(d for d, _ in kinds["prefill"])
    return sum(t for _, t in kinds["prefill"]) / seconds if seconds else None
