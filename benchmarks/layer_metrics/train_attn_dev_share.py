"""Device time under the scope ``attn`` (projections, rope, the flash kernels;
forward, recompute and backward) over device busy time, mean over chips."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    return program_trace.mean_share(shares, ("attn",)) if shares else None
