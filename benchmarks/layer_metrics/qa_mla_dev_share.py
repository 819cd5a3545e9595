"""Device time of the ops under the scope ``attn`` — latent attention whole:
projections, the latent's norm, rotary, the pool write and gather, the
up-projection or the absorbed products, scores and values, the output
projection — over device busy time. Also puts the cell's detail scopes by
name into the traced line's breakdown (``mla_trace.add_to_breakdown``)."""
from benchmarks import mla_trace, program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    if not shares:
        return None
    mla_trace.add_to_breakdown(r)
    return program_trace.mean_share(shares, ("attn",))
