"""How late the load generator ran: sent - due on the benchmark's clock, p90
over the requests due in the window. A starved generator, not a fast server,
is what a low TTFT beside a high value here would mean."""
from benchmarks import stats


def read(r):
    late = [(s.sent - s.due) * 1e3 for s in r.get("in_window", []) if s.sent is not None]
    return stats.percentile(late, 90.0)
