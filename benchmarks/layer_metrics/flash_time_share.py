"""Device time of ``flash_fwd`` + ``flash_bwd_dq`` + ``flash_bwd_dkv`` over
device busy time (worst device)."""
from benchmarks import arith


def read(r):
    red = r.get("reduced") or {}
    shares = []
    for d in red.get("devices", []):
        t = sum(d["ops"].get(k, (0, 0.0))[1] for k in arith.FLASH_MATMULS)
        if t and d["busy_s"]:
            shares.append(100.0 * t / d["busy_s"])
    return max(shares) if shares else None
