"""State bytes the traced ``pdecode`` calls *need*
(``arith_ssm.decode_needed_state_bytes``: the live lanes' h and tail — the
dispatch records' ``state_lanes`` — read once and written once a Mamba layer)
over the device time under ``attn/ssm/step`` in ``pdecode`` (the states' way
out of their slots, the update and the way back, every lane's, live or not),
over the chip's memory bandwidth."""
import statistics

from benchmarks import arith_ssm, ssm_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, records = r["model_cfg"], ssm_trace.decode_records(r)
    if records is None:
        return None
    calls = ssm_trace.program_calls(r, ("pdecode",))
    seconds = ssm_trace.seconds_in(r, ssm_trace.SSM + ("step",), ("pdecode",))
    if not calls or not seconds:
        return None
    lanes = statistics.fmean(live for _, live, _ in records)
    need = calls * arith_ssm.decode_needed_state_bytes(
        lanes, c.layers_of("mamba"), c.d_inner, c.mamba_d_state, c.mamba_d_conv)
    r.setdefault("notes", []).append(
        f"ssm in decode: {calls} calls over {lanes:.1f} live lanes need {need / 1e9:.2f} GB of "
        f"states, {seconds:.3f} s under attn/ssm/step")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
