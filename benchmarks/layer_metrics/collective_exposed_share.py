"""Time of all-reduce / all-gather / reduce-scatter / collective-permute
during which no compute runs on that device, over step time; worst device."""


def read(r):
    red = r.get("reduced") or {}
    if r["kind"] != "training":
        return None
    shares = [
        100.0 * d["collective_exposed_s"] / d["module_s"]
        for d in red.get("devices", []) if d["module_s"]
    ]
    return max(shares) if shares else None
