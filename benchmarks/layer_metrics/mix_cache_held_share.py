"""Cache bytes the live lanes hold over the bytes the same contexts would hold
were every layer a full one (``arith_window.held_share``), summed over the
decode dispatches of the traced segment: a lane holds its context's rows in
the full layers (the dispatch records' ``rows``) and one whole ring in the
window layers (the ``setup`` record's ``cache_kinds``: layers, rows a lane,
row bytes as the device lays them out). A lane of context n reads
(2 n + 3 x 1,024) / (5 n): 47 % at 8.4k, 60 % at 3k and **over 100 % under
1,024** (a context shorter than its ring) — a ratio, not a share of anything it is part of; what a whole ring a
lane costs the short lanes is the number a shared window pool would be judged
by. Lanes mid-prefill are not in a decode record and are not counted."""
from benchmarks import arith_window, window_trace


def read(r):
    kinds, records = window_trace.cache_kinds(r), window_trace.decode_records(r)
    if kinds is None or records is None:
        return None
    lanes, rows = sum(rec[0] for rec in records), sum(rec[1] for rec in records)
    if not rows:
        r.setdefault("notes", []).append("no decode dispatch in the traced segment: held share reads 0")
        return 0.0
    full, window = kinds["full"], kinds["window"]
    r.setdefault("notes", []).append(
        f"cache held: {len(records)} decode dispatches, mean context {rows / lanes:.0f} over "
        f"{lanes / len(records):.1f} live lanes, ring {window['rows_per_lane']} rows a lane")
    return 100.0 * arith_window.held_share(
        rows, lanes, full["layers"], full["row_bytes"], window["layers"], window["row_bytes"],
        window["rows_per_lane"])
