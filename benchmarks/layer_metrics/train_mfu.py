"""Required FLOPs (6 x matmul parameters + causal-halved attention, nothing
recomputed) x tokens/s over chips x peak."""
from benchmarks import arith, stats


def read(r):
    if r["kind"] != "training" or r.get("peaks") is None:
        return None
    c = r["model_cfg"]
    per_token = arith.train_flops_per_token(
        arith.matmul_params(r["param_shapes"]), c.num_layers, c.num_heads,
        c.head_dim, c.max_seq_len,
    )
    tokens_per_s = r["tokens_per_step"] / stats.median(r["periods"])
    return 100.0 * arith.mfu(tokens_per_s, per_token, r["chips"], r["peaks"].bf16_flops)
