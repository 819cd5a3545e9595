"""(Token, expert) pairs that requests' tokens asked for over the pairs the
expert blocks computed, across the dispatches of the traced segment: the
program's routing counters (``EngineTracer.routed``; bucket padding and idle
lanes are not counted as asked for). An all-experts call computes every
expert for every row of its bucket (k of E useful on a full bucket), a
selective call k experts for every lane (all useful when no lane idles); the
reading is their mix, weighted by pairs. The note gives the same share with
every row counted as asked for: what the dispatch paths alone cost."""
from benchmarks import moe_trace


def read(r):
    rows = moe_trace.routed_in_trace(r)
    if rows is None:
        return None
    c = r["model_cfg"]
    by_path = {}
    for _, _, paths, pairs, counts in rows:
        got = by_path.setdefault("+".join(paths), [0, 0, 0, 0.0])
        got[0] += 1
        got[1] += sum(counts)
        got[2] += pairs
        got[3] += pairs if paths == ("selective",) else pairs * c.top_k / c.num_experts
    routed, computed, every_row = (sum(got[i] for got in by_path.values()) for i in (1, 2, 3))
    if not computed:
        return None
    r.setdefault("notes", []).append(
        "expert pairs asked for by live tokens / computed, by dispatch path: " + ", ".join(
            f"{path} {n} calls {a} / {b}" for path, (n, a, b, _) in sorted(by_path.items()))
        + f"; with padding and idle lanes counted as asking: {100.0 * every_row / computed:.2f} %")
    return 100.0 * routed / computed
