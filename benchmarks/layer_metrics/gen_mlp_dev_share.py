"""Device time of the ops under the scope ``mlp`` (the dense SwiGLU block) over
device busy time."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    return program_trace.mean_share(shares, ("mlp",)) if shares else None
