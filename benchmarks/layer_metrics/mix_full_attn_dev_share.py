"""Device time of the ops under ``attn`` ... ``full`` — the attention block
of a full layer whole: projections, rotary, the pool write, the gather of the
lane's rows up to the kv rung, scores and values, the output gate and
projection — over device busy time. Also puts the detail scopes by name into
the traced line's breakdown (``mla_trace.add_to_breakdown``)."""
from benchmarks import mla_trace, window_trace


def read(r):
    value = window_trace.kind_share(r, "full")
    if value is not None:
        mla_trace.add_to_breakdown(r)
    return value
