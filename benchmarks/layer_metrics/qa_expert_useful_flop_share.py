"""(Token, expert) pairs the live tokens asked of the experts held here over
the pairs the expert blocks computed, across the dispatches of the traced
segment. The all-held-experts path computes every held expert for every row of
its bucket: on a full bucket 8 x 32/128 = 2 of 32 are asked for, 6.25 %;
bucket padding and idle lanes ask for nothing."""
from benchmarks import mla_trace


def read(r):
    rows = mla_trace.routed_with_local(r)
    if rows is None:
        return None
    computed = sum(row[3] for row, _ in rows)
    paths = sorted({p for row, _ in rows for p in row[2]})
    r.setdefault("notes", []).append(
        f"expert dispatch paths in the traced segment: {paths}; {len(rows)} dispatches, "
        f"{computed} pairs computed")
    return 100.0 * sum(local for _, local in rows) / computed if computed else None
