"""Device time of the ops under ``moe`` ... ``shared`` (the shared expert's
SwiGLU over every token) over device busy time."""
from benchmarks import mla_trace


def read(r):
    return mla_trace.share(r, ("moe", "shared"))
