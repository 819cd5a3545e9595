"""Device time of the ops under ``mhc`` — the multi-stream residual: the
streams' norm and the coefficient products (``mhc/coeff``), the Sinkhorn
rounds (``mhc/sinkhorn``), the pre-collapse, post-spread and residual mix
(``mhc/mix``) — over device busy time, ``pdecode`` and prefill together. Also
puts the residual's scopes and the query latent's by name into the traced
line's breakdown (``residual_trace.add_to_breakdown``)."""
from benchmarks import mla_trace, residual_trace


def read(r):
    value = mla_trace.share(r, residual_trace.MHC)
    if value is not None:
        residual_trace.add_to_breakdown(r)
    return value
