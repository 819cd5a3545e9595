"""Device time of the ops under ``attn`` ... ``qk_norm`` (the joint RMSNorm
of the projected query and key) over device busy time."""
from benchmarks import moe_trace


def read(r):
    got = moe_trace.path_seconds(r, ("attn", "qk_norm"))
    if got is None or not got[0]:
        return None
    return 100.0 * got[0] / got[1]
