"""1 - union of device-op intervals / traced window, worst device."""


def read(r):
    red = r.get("reduced") or {}
    if not red.get("devices"):
        return None
    return 100.0 * red["idle_share_worst"]
