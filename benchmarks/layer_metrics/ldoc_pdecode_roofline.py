"""Bytes one ``pdecode`` *needs* — every layer's weights and the head once
for all lanes (``arith_residual.decode_weight_bytes``) and the live lanes'
latent rows once a layer as counted (the dispatch records' ``rows``;
``arith_mla.decode_needed_latent_bytes``) — over the mean device time of the
traced ``pdecode`` runs, over the chip's memory bandwidth: the share of the
whole step, which bounds every later claim on this cell's decode."""
import statistics

import numpy as np

from benchmarks import arith_mla, arith_residual, mla_trace, program_trace


def read(r):
    c = r.get("model_cfg")
    if r.get("kind") != "serving" or r.get("peaks") is None or not hasattr(c, "hc_mult"):
        return None
    runs, rows = program_trace.program_run_ms(r, "pdecode"), mla_trace.decode_rows(r)
    if not runs or rows is None:       # as `qa_mla_decode_roofline`: no `pdecode` call, nothing to read
        return None
    item = np.dtype(c.dtype).itemsize
    weights = arith_residual.decode_weight_bytes(
        lanes=int(r["cell"].traffic["engine"]["lanes"]), hidden=c.hidden_size, heads=c.num_heads,
        q_rank=c.q_lora_rank, kv_rank=c.kv_lora_rank, d_nope=c.qk_nope_head_dim,
        d_rope=c.qk_rope_head_dim, d_v=c.v_head_dim, streams=c.hc_mult,
        dense_layers=c.first_k_dense, dense_width=c.intermediate_size,
        expert_layers=c.num_layers - c.first_k_dense, num_experts=c.num_experts, top_k=c.top_k,
        expert_width=c.moe_intermediate_size,
        shared_width=c.num_shared_experts * c.moe_intermediate_size,
        vocab=c.vocab_size, itemsize=item)
    cache = arith_mla.decode_needed_latent_bytes(
        statistics.fmean(rows), c.num_layers, c.kv_lora_rank, c.qk_rope_head_dim, itemsize=item)
    seconds = statistics.fmean(runs) / 1e3
    r.setdefault("notes", []).append(
        f"a decode step needs {weights / 1e9:.2f} GB of weights + {cache / 1e9:.3f} GB of latent rows "
        f"({statistics.fmean(rows):.0f} live rows), runs {seconds * 1e3:.2f} ms")
    return 100.0 * (weights + cache) / seconds / r["peaks"].hbm_bytes_per_s
