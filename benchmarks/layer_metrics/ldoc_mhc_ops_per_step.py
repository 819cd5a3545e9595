"""Device operations under ``mhc`` in one ``pdecode`` call (those of the
traced window's ``pdecode`` programs over the calls): the latency the
residual's share of the time hides — its bytes are next to nothing, each of
its operations is a launch."""
from benchmarks import moe_trace, residual_trace


def read(r):
    if r.get("kind") != "serving" or not residual_trace.names_residual():
        return None
    got = residual_trace.under(r, residual_trace.MHC, ("pdecode",))
    if got is None:
        return None
    calls = moe_trace.program_calls(r, ("pdecode",))
    if not calls:       # the cell's `trace_s` outlasts its longest stretch of prefill alone
        return None
    r.setdefault("notes", []).append(
        f"the residual in decode: {got[1]} device operations, {got[0] * 1e3:.2f} ms under mhc "
        f"in {calls} pdecode calls")
    return got[1] / calls
