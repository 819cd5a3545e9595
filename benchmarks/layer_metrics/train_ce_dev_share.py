"""Device time under ``ce`` or ``lm_head`` (the head's matmul and the loss,
each op counted once) over device busy time, mean over chips."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    return program_trace.mean_share(shares, ("ce", "lm_head")) if shares else None
