"""Device time of the ops under ``attn/ssm`` — a Mamba mixer's two
projections, its convolution, ``x_proj`` with the inner norms and ``dt_proj``,
a chunk's scan, a decode step's pass over the slots — over device busy time,
prefill and decode together. Also puts the mixers' scopes by name into the
traced line's breakdown (``ssm_trace.add_to_breakdown``)."""
from benchmarks import ssm_trace


def read(r):
    value = ssm_trace.share(r, ssm_trace.SSM)
    if value is not None:
        ssm_trace.add_to_breakdown(r)
    return value
