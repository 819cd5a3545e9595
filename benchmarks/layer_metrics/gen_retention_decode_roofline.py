"""State bytes the traced ``pdecode`` calls *need*
(``arith_retention.decode_needed_state_bytes``: the live lanes' states — the
dispatch records' ``rows`` — read once and written once a layer at the
narrowest φ) over the device time under ``attn/retention`` in ``pdecode`` (the
pass over the states, ``step``, and φ of the one row a lane, ``expand``), over
the chip's memory bandwidth."""
import statistics

from benchmarks import arith_retention, moe_trace, retention_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c, lanes = r["model_cfg"], retention_trace.live_lanes(r)
    if lanes is None:
        return None
    calls = moe_trace.program_calls(r, ("pdecode",))
    seconds = retention_trace.seconds_in(r, retention_trace.RETENTION, ("pdecode",))
    if not calls or not seconds:
        return None
    need = calls * arith_retention.decode_needed_state_bytes(
        statistics.fmean(lanes), c.num_layers, c.num_kv_heads, c.head_dim)
    r.setdefault("notes", []).append(
        f"retention in decode: {calls} calls over {statistics.fmean(lanes):.1f} live lanes need "
        f"{need / 1e9:.2f} GB of states, {seconds:.3f} s under attn/retention")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
