"""FLOPs the all-experts path *executes* in the traced ``pctx``/``psfx``
calls (``arith_moe.all_experts_flops``: every expert over every token of the
bucket, and the combine) over the device time the experts cost in those
programs (``moe_trace.expert_seconds``: everything under ``moe/experts`` and
the copies of expert weights the compiler leaves outside every block), over
the chip's bf16 peak. Only where the program says those calls took the
all-experts path."""
from benchmarks import arith_moe, moe_trace

PREFILL = ("pctx", "psfx")


def read(r):
    rows = moe_trace.routed_in_trace(r)
    if rows is None or r.get("peaks") is None:
        return None
    paths = moe_trace.paths_by_kind(rows)
    if any(paths.get(kind, {"all"}) != {"all"} for kind in PREFILL):
        return None
    bucket, calls = moe_trace.prefill_bucket(r), moe_trace.program_calls(r, PREFILL)
    seconds = moe_trace.expert_seconds(r, PREFILL)
    if bucket is None or not calls or not seconds:
        return None
    c = r["model_cfg"]
    flops = calls * arith_moe.all_experts_flops(
        bucket, c.hidden_size, c.intermediate_size, c.num_experts, c.num_layers)
    r.setdefault("notes", []).append(
        f"experts in prefill: {calls} calls of {bucket} tokens, {flops / 1e12:.2f} TFLOP executed in "
        f"{seconds:.3f} s of expert time")
    return 100.0 * flops / seconds / r["peaks"].bf16_flops
