"""Device idle time while no ``graft.step`` annotation is open (the server's
loop is in ``drive.pump`` or ``drive.yield``, serving sockets) over the traced
window; with ``idle_in_step_share`` it adds up to ``device_idle_share``."""
from benchmarks import program_trace


def read(r):
    split = program_trace.idle_split(r)
    return split["between"] if split else None
