"""Pool blocks the allocator evicted during the measured window (the
``evictions`` counter of the engine's snapshots at the window's edges) per
request due in it: how much cached prefix the working set pushed out."""


def read(r):
    snaps = r.get("snapshots") or {}
    first, last = snaps.get("open") or {}, snaps.get("close") or {}
    if "evictions" not in first or "evictions" not in last or not r.get("attempted"):
        return None
    return (last["evictions"] - first["evictions"]) / r["attempted"]
