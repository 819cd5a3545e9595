"""Expert-weight bytes the traced ``pdecode`` calls *need*
(``arith_moe.decode_needed_weight_bytes``: in every layer one read of each
expert a step of this many lanes can route to) over the device time the
experts cost in ``pdecode`` (``moe_trace.expert_seconds``: everything under
``moe/experts`` and the copies of expert weights the compiler leaves outside
every block — the selective path's stack copies and gather buffers), over the
chip's memory bandwidth."""
import numpy as np

from benchmarks import arith_moe, moe_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c = r["model_cfg"]
    if not getattr(c, "num_experts", 0):
        return None
    calls = moe_trace.program_calls(r, ("pdecode",))
    seconds = moe_trace.expert_seconds(r, ("pdecode",))
    if not calls or not seconds:
        return None
    lanes = int(r["cell"].traffic["engine"]["lanes"])
    need = calls * arith_moe.decode_needed_weight_bytes(
        lanes, c.top_k, c.num_experts, c.hidden_size, c.intermediate_size, c.num_layers,
        itemsize=np.dtype(c.dtype).itemsize)
    r.setdefault("notes", []).append(
        f"experts in decode: {calls} calls of {lanes} lanes need {need / 1e9:.2f} GB of expert weights, "
        f"{seconds:.3f} s of expert time")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
