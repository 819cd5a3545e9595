"""FLOPs the traced ``pctx``/``psfx`` calls *execute* under
``attn/retention/chunk`` (``arith_retention.chunk_retention_flops`` at each
dispatch record's bucket and the program's own φ) over the device time under
``attn/retention`` in those programs (``chunk`` and ``expand``), over the
chip's bf16 peak; 0 where the traced segment holds no prefill call."""
import statistics

from benchmarks import arith_retention, moe_trace, retention_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None or not retention_trace.named():
        return None
    c, buckets = r["model_cfg"], retention_trace.prefill_buckets(r)
    if buckets is None:
        return 0.0 if retention_trace.no_prefill_in_segment(r) else None
    calls = moe_trace.program_calls(r, retention_trace.PREFILL)
    seconds = retention_trace.seconds_in(r, retention_trace.RETENTION, retention_trace.PREFILL)
    if not calls or not seconds:     # dispatches recorded, their device ops outside the window
        return 0.0
    flops = calls * c.num_layers * statistics.fmean(
        arith_retention.chunk_retention_flops(
            bucket, c.num_heads, c.num_kv_heads, c.head_dim, c.feature_width)
        for bucket in buckets)
    r.setdefault("notes", []).append(
        f"retention in prefill: {calls} calls, recorded buckets {sorted(set(buckets))}, "
        f"{flops / 1e12:.2f} TFLOP executed in {seconds:.3f} s under attn/retention")
    return 100.0 * flops / seconds / r["peaks"].bf16_flops
