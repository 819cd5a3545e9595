"""Expert-weight bytes the traced ``pdecode`` calls *need*
(``arith_moe.decode_needed_weight_bytes``: in every expert layer one read of
each expert a step of this many lanes can route to — 32 lanes x 8 choices
reach all 256) over the device time the experts cost in ``pdecode``
(``moe_trace.expert_seconds``: everything under ``moe/experts`` and any copy
of expert weights the compiler leaves outside every block), over the chip's
memory bandwidth."""
from benchmarks import arith_moe, window_trace


def read(r):
    if r.get("peaks") is None or not window_trace.names_kinds():
        return None
    got = window_trace.decode_calls(r)
    if got is None:
        return None
    calls, c = got[0], r["model_cfg"]
    seconds = window_trace.expert_decode_seconds(r)
    if not calls or not seconds:
        return 0.0
    lanes = int(r["cell"].traffic["engine"]["lanes"])
    need = calls * arith_moe.decode_needed_weight_bytes(
        lanes, c.top_k, c.num_experts, c.hidden_size, c.moe_intermediate_size,
        c.mlp_layer_types.count("sparse"), itemsize=window_trace._itemsize(c))
    r.setdefault("notes", []).append(
        f"experts in decode: {calls} calls of {lanes} lanes need {need / 1e9:.2f} GB of expert weights, "
        f"{seconds:.3f} s of expert time")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
