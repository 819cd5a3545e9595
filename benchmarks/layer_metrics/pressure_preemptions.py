"""Requests the engine bumped back to its queue during the measured window
because the pool could not grow them (the ``preemptions`` counter of the
engine's snapshots at the window's edges)."""


def read(r):
    snaps = r.get("snapshots") or {}
    first, last = snaps.get("open") or {}, snaps.get("close") or {}
    if "preemptions" not in first or "preemptions" not in last:
        return None
    return float(last["preemptions"] - first["preemptions"])
