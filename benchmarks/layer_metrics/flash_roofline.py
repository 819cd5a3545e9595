"""The least time the chip could take for the flash kernels' calls (FLOPs
and bytes from shapes, the larger of the two bounds) over the time they took;
device 0. Which bound applies goes into the run's notes."""
from benchmarks import arith


def read(r):
    red = r.get("reduced") or {}
    if r["kind"] != "training" or not red.get("devices") or r.get("peaks") is None:
        return None
    c, lay = r["model_cfg"], r["layout"]
    tp = int(lay.get("tp", 1))
    micro = r["tokens_per_step"] // c.max_seq_len // int(r["cell"].traffic["microbatches"])
    dev = red["devices"][0]
    least, took, bounds = 0.0, 0.0, set()
    for kernel in arith.FLASH_MATMULS:
        count, seconds = dev["ops"].get(kernel, (0, 0.0))
        if not count:
            continue
        flops, nbytes = arith.flash_call_cost(
            kernel, micro, c.num_heads // tp, max(c.num_kv_heads // tp, 1),
            c.max_seq_len, c.head_dim,
        )
        t, bound = arith.roofline_seconds(
            flops, nbytes, r["peaks"].bf16_flops, r["peaks"].hbm_bytes_per_s
        )
        least += count * t
        took += seconds
        bounds.add(bound)
    if not took:
        return None
    r.setdefault("notes", []).append(f"flash kernels are {'/'.join(sorted(bounds))}-bound")
    return 100.0 * least / took
