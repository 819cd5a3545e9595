"""Bytes the traced ``pctx``/``psfx`` calls *need* under ``attn/ssm/conv`` +
``attn/ssm/scan`` (``arith_ssm.prefill_needed_bytes``: a real row's operands a
Mamba layer, a lane's h and tail once in and once out a call) over the device
time under those two scopes in those programs, over the chip's memory
bandwidth; 0 where the traced segment holds no prefill call. The scan is a
loop over rows whose trip is bound by the vector unit and the loop's own
overhead, not by bytes: it reads low here, and a kernel that keeps h in
vector memory across a chunk's rows raises it."""
from benchmarks import arith_ssm, ssm_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None or not ssm_trace.named():
        return None
    c, rows = r["model_cfg"], ssm_trace.prefill_rows(r)
    if rows is None:
        return 0.0 if ssm_trace.no_prefill_in_segment(r) else None
    calls = ssm_trace.program_calls(r, ssm_trace.PREFILL)
    seconds = sum(ssm_trace.seconds_in(r, ssm_trace.SSM + (leaf,), ssm_trace.PREFILL) or 0.0
                  for leaf in ("conv", "scan"))
    if not calls or not seconds:     # dispatches recorded, their device ops outside the window
        return 0.0
    per_call = sum(rows) / len(rows)
    need = arith_ssm.prefill_needed_bytes(
        calls * per_call, calls, c.layers_of("mamba"), c.d_inner, c.mamba_d_state, c.mamba_d_conv)
    r.setdefault("notes", []).append(
        f"ssm in prefill: {calls} calls of {per_call:.0f} real rows need {need / 1e9:.2f} GB, "
        f"{seconds:.3f} s under attn/ssm/conv + scan")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
