"""Device time of the copies of expert weights that sit outside every block
(``moe_trace.expert_copy_seconds``: a layer's expert stack copied out of the
layer scan, the buffers the selective gather fills) in the three paged
programs, over device busy time. The shared readers book these to the
program's root, so ``rag_moe_dev_share`` does not see them."""
from benchmarks import moe_trace


def read(r):
    copies = moe_trace.expert_copy_seconds(r, ("pctx", "psfx", "pdecode"))
    if copies is None:
        return None
    busy = r["reduced"]["devices"][0]["busy_s"]
    r.setdefault("notes", []).append(
        "expert weights copied outside every block (s): "
        + (", ".join(f"{k} {v:.3f}" for k, v in sorted(copies.items(), key=lambda kv: -kv[1])[:6]) or "none"))
    return 100.0 * sum(copies.values()) / busy if busy else None
