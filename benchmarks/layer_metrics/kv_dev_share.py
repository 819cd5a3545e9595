"""Device time of the ops under ``attn/kv_write`` and ``attn/kv_read`` (the
pool update and the gather) over device busy time. A pool-sized copy the
compiler makes for the layer scan carries no scope of its own and is booked to
the program's root: the ``scopes`` note shows it there."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    return program_trace.mean_share(shares, ("attn/kv_write", "attn/kv_read")) if shares else None
