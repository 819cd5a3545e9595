"""Expert-weight bytes the traced ``pdecode`` calls *need* — in every expert
layer one read of each expert held here (``arith_moe.expert_weight_bytes``;
64 lanes x 8 choices reach all 32) — over the device time the experts cost in
``pdecode`` (``moe_trace.expert_seconds``: everything under ``moe/experts``
and any copy of expert weights the compiler leaves outside every block), over
the chip's memory bandwidth."""
import types

import numpy as np

from benchmarks import arith_moe, moe_trace


def read(r):
    if r.get("kind") != "serving" or r.get("peaks") is None:
        return None
    c = r["model_cfg"]
    if not hasattr(c, "experts_held"):
        return None
    held = c.experts_held or c.num_experts
    # the model's one expert width: the copies outside every block are found by it
    width = types.SimpleNamespace(
        num_experts=held, hidden_size=c.hidden_size, intermediate_size=c.moe_intermediate_size)
    calls = moe_trace.program_calls(r, ("pdecode",))
    seconds = moe_trace.expert_seconds({**r, "model_cfg": width}, ("pdecode",))
    if not calls or not seconds:
        return None
    lanes = int(r["cell"].traffic["engine"]["lanes"])
    layers = c.num_layers - c.first_k_dense
    experts = min(lanes * c.top_k, held)
    need = calls * layers * experts * arith_moe.expert_weight_bytes(
        c.hidden_size, c.moe_intermediate_size, itemsize=np.dtype(c.dtype).itemsize)
    r.setdefault("notes", []).append(
        f"experts in decode: {calls} calls need {need / 1e9:.2f} GB of the held experts' weights, "
        f"{seconds:.3f} s of expert time")
    return 100.0 * need / seconds / r["peaks"].hbm_bytes_per_s
