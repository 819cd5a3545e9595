"""Device time of the ops under ``attn`` ... ``latent_up`` (``W_UKV`` over the
rows a prefill call attends: the fresh block in ``pctx``, the cached rows in
an expanded ``psfx``) over device busy time."""
from benchmarks import mla_trace


def read(r):
    return mla_trace.share(r, ("attn", "latent_up"))
