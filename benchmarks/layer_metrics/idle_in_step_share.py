"""Device idle time while a ``graft.step`` annotation is open (the engine is
inside ``step()``) over the traced window, on the device ``device_idle_share``
reads. The notes give the split by innermost tracer span and the clock join's
error."""
from benchmarks import program_trace


def read(r):
    split = program_trace.idle_split(r)
    return split["in_step"] if split else None
