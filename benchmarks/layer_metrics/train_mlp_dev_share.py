"""Device time under the scope ``mlp`` over device busy time, mean over
chips."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    return program_trace.mean_share(shares, ("mlp",)) if shares else None
