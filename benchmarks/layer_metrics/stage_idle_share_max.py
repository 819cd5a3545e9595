"""Largest per-device share of train-step time in which no compute op runs
(waiting on a pipeline neighbour, an exposed collective, or nothing)."""


def read(r):
    red = r.get("reduced") or {}
    if r["kind"] != "training":
        return None
    shares = [
        100.0 * d["no_compute_in_modules_s"] / d["module_s"]
        for d in red.get("devices", []) if d["module_s"]
    ]
    return max(shares) if shares else None
