"""Coefficient of variation (standard deviation over mean) of the tokens
routed to each expert, summed over layers and over the dispatches of the
traced segment: the program's routing counters, which count the rows that
carry a request's token (not bucket padding, not idle lanes)."""
import statistics

from benchmarks import moe_trace


def read(r):
    rows = moe_trace.routed_in_trace(r)
    if rows is None:
        return None
    per_expert = moe_trace.tokens_per_expert(rows)
    mean = statistics.fmean(per_expert)
    return 100.0 * statistics.pstdev(per_expert) / mean if mean else None
