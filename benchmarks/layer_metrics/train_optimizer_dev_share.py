"""Device time under ``optimizer`` or ``grad_clip`` over device busy time,
mean over chips."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    return program_trace.mean_share(shares, ("optimizer", "grad_clip")) if shares else None
