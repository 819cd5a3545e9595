"""FLOPs the traced ``pctx`` / ``psfx`` calls *need* under
``attn/lightning/chunk`` (``arith_sala.lightning_chunk_flops``: a real row's
causal half of the chunk's two square products, its read of the carried state
and its share of the state's update, a head a Lightning layer) over the device
time under that scope in those programs (the state's way out of its slot and
back included), over the chip's bf16 peak; 0 where the traced segment holds no
prefill call. The square products run whole and masked, the decay matrix is
made by the vector unit and the state's products run in float32: it reads
low."""
from benchmarks import arith_sala, sala_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None or not sala_trace.named():
        return None
    c, rows = r["model_cfg"], sala_trace.prefill_rows(r)
    if rows is None:
        return 0.0 if sala_trace.no_prefill_in_segment(r) else None
    calls = sala_trace.program_calls(r, sala_trace.PREFILL)
    seconds = sala_trace.seconds_in(r, sala_trace.LIGHTNING + ("chunk",), sala_trace.PREFILL)
    if not calls or not seconds:     # dispatches recorded, their device ops outside the window
        return 0.0
    per_call = sum(rows) / len(rows)
    need = arith_sala.lightning_chunk_flops(
        calls * per_call, calls, c.layers_of("lightning-attn"), c.lightning_heads, c.head_dim)
    r.setdefault("notes", []).append(
        f"lightning in prefill: {calls} calls of {per_call:.0f} real rows need {need / 1e12:.3f} TFLOP, "
        f"{seconds:.3f} s under attn/lightning/chunk")
    return 100.0 * need / seconds / r["peaks"].bf16_flops
