"""FLOPs the traced ``pctx``/``psfx`` calls *execute* under ``attn/latent_up``
and ``attn/sdpa`` (``arith_mla.prefill_attention_flops`` at each dispatch
record's bucket and ``kv_bucket``) over the device time under those two scopes
in those programs, over the chip's bf16 peak; 0 where the traced segment
holds no prefill call (``mla_trace.no_prefill_in_segment``)."""
import statistics

from benchmarks import arith_mla, mla_trace, moe_trace

PREFILL = ("pctx", "psfx")


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, recorded = r["model_cfg"], mla_trace.prefill_calls(r)
    if not hasattr(c, "kv_lora_rank"):
        return None
    if recorded is None:
        return 0.0 if mla_trace.no_prefill_in_segment(r) else None
    calls = moe_trace.program_calls(r, PREFILL)
    up, sdpa = (mla_trace.seconds_in(r, ("attn", s), PREFILL) for s in ("latent_up", "sdpa"))
    if not calls or not sdpa:       # dispatches recorded, their device ops outside the window
        return 0.0
    seconds = (up or 0.0) + sdpa
    flops = calls * c.num_layers * statistics.fmean(
        arith_mla.prefill_attention_flops(
            bucket, kv, c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim)
        for bucket, kv in recorded)
    r.setdefault("notes", []).append(
        f"latent attention in prefill: {calls} calls, recorded (bucket, kv) {sorted(set(recorded))}, "
        f"{flops / 1e12:.2f} TFLOP executed in {seconds:.3f} s under latent_up + sdpa")
    return 100.0 * flops / seconds / r["peaks"].bf16_flops
