"""Median step period on the host clock (loss ready to next loss ready)."""
from benchmarks import stats


def read(r):
    return stats.median(r["periods"]) * 1e3 if r["kind"] == "training" else None
