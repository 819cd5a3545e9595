"""(Token, expert) pairs the live tokens' routers chose *among the experts
this chip holds* over all the pairs they chose, across the dispatches of the
traced segment (the routing tap's ``routed_local`` beside ``routed``). A
uniform router over 128 experts with 32 held gives 25 %."""
from benchmarks import mla_trace


def read(r):
    rows = mla_trace.routed_with_local(r)
    if rows is None:
        return None
    chosen = sum(sum(row[4]) for row, _ in rows)
    return 100.0 * sum(local for _, local in rows) / chosen if chosen else None
