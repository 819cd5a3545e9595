"""Device time of the ops under ``attn/retention`` — φ of q and k, a chunk's
weights, the state's read and its update, a decode step's pass over the states
— over device busy time. Also puts the attention block's scopes by name into
the traced line's breakdown (``retention_trace.add_to_breakdown``)."""
from benchmarks import retention_trace


def read(r):
    value = retention_trace.share(r, retention_trace.RETENTION)
    if value is not None:
        retention_trace.add_to_breakdown(r)
    return value
