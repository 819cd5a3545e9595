"""The MiniCPM-SALA configuration as the benchmark runs it: the file against
the catalog's published keys (the two that are cut, the sizes assumed), the
family module (file -> ``SalaConfig``, and the reference's own view of the
order and the selection), the plain reference against the program's training
model at the rehearsal's size and against facts worked out by hand, the
benchmark's arithmetic against the program's own counts, and the cell's sizes."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arith_sala, spec

RTOL = ATOL = 1e-4
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
PUBLISHED_MIXERS = [SPARSE] + [LIGHTNING] * 8 + [SPARSE] + [LIGHTNING] * 6 + [SPARSE, SPARSE] + [
    LIGHTNING] * 4 + [SPARSE] + [LIGHTNING] * 6 + [SPARSE] * 3
PUBLISHED = {      # the catalog row's `config`, every key
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32,
    "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala", "mixer_types": PUBLISHED_MIXERS,
    "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
}
CELL = "sala-longctx-steady"


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("minicpm_sala")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(spec.HERE, "configs", "minicpm-sala-1chip.json")) as f:
        return json.load(f)


def test_the_file_has_every_published_key_and_cuts_depth_alone(cfg):
    assert len(PUBLISHED_MIXERS) == 32 and PUBLISHED_MIXERS.count(SPARSE) == 8
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == {"num_hidden_layers", "mixer_types"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 8 and cfg["mixer_types"] == PUBLISHED_MIXERS[9:17]
    assert cfg["mixer_types"] == [SPARSE] + [LIGHTNING] * 6 + [SPARSE]              # the published 1 : 3
    assert cfg["reduced"]["mixer_types"]["published"] == PUBLISHED_MIXERS
    bench = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    row = next(c for c in bench["configs"] if c["name"] == "minicpm-sala-1chip")
    assert row["reduced"] == ["num_hidden_layers", "mixer_types"] and row["source"] == cfg["source"]
    sparse = cfg["assumed"]["sparse_config"]
    assert {k: sparse[k] for k in ("kernel_size", "kernel_stride", "block_size", "topk", "init_blocks",
                                   "window_size")} == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64, "init_blocks": 1, "window_size": 2048}
    for key in ("lightning_decay", "gates", "output_norm", "qk_norm", "mup", "selection_at_every_row",
                "state_dtype", "compute_dtype", "torch_dtype"):
        assert len(cfg["assumed"][key]) > 40, key          # each with its argument
    assert "DEPARTURE" in cfg["assumed"]["selection_at_every_row"] and len(sparse["doc"]) > 40
    assert cfg["chips"] == 1 and cfg["layout"] == {"tp": 1} and "four pipeline stages" in cfg["deployment"].lower()


def test_the_family_builds_the_programs_config_at_published_widths(fam, cfg):
    c = fam.model_config(cfg, rehearsal=False, max_seq_len=33280)
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim, c.lightning_heads) == (4096, 32, 2, 128, 32)
    assert (c.intermediate_size, c.vocab_size, c.num_layers, c.max_seq_len) == (16384, 73448, 8, 33280)
    assert (c.scale_emb, c.scale_depth, c.mup_denominator, c.dim_model_base) == (12.0, 1.4, 32, 256)
    assert c.rms_norm_eps == 1e-6 and not c.tie_word_embeddings and c.dtype == jnp.bfloat16 and c.rope_theta == 1e4
    assert c.mixer_types == tuple(cfg["mixer_types"]) and c.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (c.kernel_size, c.kernel_stride, c.sparse_block_size, c.sparse_topk, c.sparse_init_blocks,
            c.sparse_window) == (32, 16, 64, 64, 1, 2048)
    assert cfg["state_bytes_per_lane"] == 6 * c.state_bytes_per_layer() == 6 * 32 * 128 * 128 * 4
    # the reference's view: the file's own order and sizes under the reference's names
    ref = fam.reference_config(c)
    assert ref["mixer_types"] == cfg["mixer_types"] and ref["topk"] == 64 and ref["window_size"] == 2048
    assert (ref["kernel_size"], ref["kernel_stride"], ref["block_size"], ref["init_blocks"]) == (32, 16, 64, 1)
    # the arithmetic of the cut: 2 x 253.8 M + 6 x 285.2 M + 601.7 M = 2,820 M parameters
    shapes = jax.eval_shape(fam.train_model(c).init, jax.random.key(0))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    swiglu = 3 * 4096 * 16384
    assert count(shapes["sparse_layers"]["attn"]) == 2 * (3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128)
    assert count(shapes["lightning_layers"]["attn"]) == 6 * (5 * 4096 * 4096 + 2 * 128 + 4096)
    assert count(shapes["sparse_layers"]["mlp"]) == 2 * swiglu and count(shapes["lightning_layers"]["mlp"]) == 6 * swiglu
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 2 * 73448 * 4096
    assert count(shapes) // 10 ** 6 == 2820 and 2 * count(shapes) // 10 ** 7 == 564       # 5.64 GB in bf16
    with pytest.raises(ValueError, match="the file says otherwise"):
        fam.model_config({**cfg, "attn_use_rope": True}, rehearsal=False)
    with pytest.raises(ValueError, match="the file says otherwise"):
        fam.model_config({**cfg, "num_hidden_layers": 9}, rehearsal=False)


def test_reference_matches_the_programs_model_at_the_rehearsals_size(fam, cfg):
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert model_cfg.num_layers == 5 and model_cfg.layer_kinds.count(SPARSE) == 2
    params = jax.jit(fam.train_model(model_cfg).init)(jax.random.key(3))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])
    ids = jnp.asarray(np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 90)), jnp.int32)
    ref_cfg = fam.reference_config(model_cfg)
    assert ref_cfg["mixer_types"] == list(model_cfg.mixer_types) and ref_cfg["topk"] == model_cfg.sparse_topk
    model = fam.train_model(model_cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids)
        want_loss = float(jax.jit(lambda p, i: fam.reference.loss(p, ref_cfg, i))(params, ids))
        got, got_loss = jax.jit(model.__call__)(params, ids), float(jax.jit(model.loss)(params, ids, ids))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    assert abs(got_loss - want_loss) < 1e-4 * abs(want_loss)


def test_the_references_two_mixers_by_hand(fam):
    """The Lightning recurrence against a double loop in numpy, and the sparse
    layer against plain causal attention while every block is inside the
    budget — and apart from it once blocks are dropped."""
    ref = fam.reference
    rng = np.random.default_rng(1)
    n, d, s, h = 2, 4, 7, 8
    p = {"qkv": {name: jnp.asarray(rng.normal(size=(h, n * d)), jnp.float32) for name in ("q_kernel", "k_kernel", "v_kernel")},
         "q_norm": {"scale": jnp.ones((d,))}, "k_norm": {"scale": jnp.ones((d,))},
         "out_norm": {"scale": jnp.ones((n * d,))},
         "gate": {"kernel": jnp.asarray(rng.normal(size=(h, n * d)), jnp.float32)},
         "o": {"kernel": jnp.eye(n * d, dtype=jnp.float32)}}
    x = jnp.asarray(rng.normal(size=(1, s, h)), jnp.float32)
    cfg = {"lightning_heads": n, "head_dim": d, "rms_norm_eps": 1e-6, "rope_theta": 10000.0}
    got = np.asarray(ref._lightning(x, p, cfg))[0]
    q, k, v = (np.asarray(a)[0] for a in ref._project(x, p, n, n, d, 1e-6))
    sin, cos = (np.asarray(a) for a in ref.rope_tables(d, s, 10000.0))
    rot = lambda a: a * cos[:, None] + np.concatenate([-a[..., d // 2:], a[..., :d // 2]], -1) * sin[:, None]  # noqa: E731
    q, k = rot(q), rot(k)
    lam = np.exp(-(2.0 ** (-8.0 * np.arange(1, n + 1) / n)))
    o = np.zeros((s, n, d))
    for t in range(s):
        for j in range(t + 1):
            o[t] += (lam ** (t - j))[:, None] * np.einsum("nd,nd->n", q[t], k[j])[:, None] * v[j]
    o = (o / np.sqrt(d)).reshape(s, n * d)
    o = o / np.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-6)
    gate = 1.0 / (1.0 + np.exp(-np.asarray(x[0] @ p["gate"]["kernel"])))
    np.testing.assert_allclose(got, o * gate, rtol=1e-4, atol=1e-5)

    sparse_cfg = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": d, "rms_norm_eps": 1e-6,
                  "kernel_size": 4, "kernel_stride": 2, "block_size": 4, "topk": 3, "init_blocks": 1, "window_size": 4}
    ps = {**p, "qkv": {"q_kernel": jnp.asarray(rng.normal(size=(h, 4 * d)), jnp.float32),
                       "k_kernel": p["qkv"]["k_kernel"], "v_kernel": p["qkv"]["v_kernel"]},
          "gate": {"kernel": jnp.asarray(rng.normal(size=(h, 4 * d)), jnp.float32)},
          "o": {"kernel": jnp.eye(4 * d, dtype=jnp.float32)}}
    xs = jnp.asarray(rng.normal(size=(1, 40, h)), jnp.float32)
    got = np.asarray(ref._sparse(xs, ps, sparse_cfg))[0]
    dense = np.asarray(ref._sparse(xs, ps, {**sparse_cfg, "topk": 10}))[0]
    q, k, v = ref._project(xs, ps, 4, 2, d, 1e-6)
    from benchmarks.reference.common import causal_attention
    plain = np.asarray(causal_attention(q, k, v))[0] / (1.0 + np.exp(-np.asarray(xs[0] @ ps["gate"]["kernel"])))
    np.testing.assert_allclose(dense, plain, rtol=1e-4, atol=1e-5)          # ten blocks, all taken
    np.testing.assert_allclose(got[:12], plain[:12], rtol=1e-4, atol=1e-5)  # three blocks behind a row: all taken
    assert np.abs(got[24:] - plain[24:]).max() > 1e-3                        # seven and more: four dropped


def test_the_reference_is_one_sequence_from_its_first_row_alone(fam):
    code = inspect.getsource(fam.reference).split('"""', 2)[2]
    for word in ("chunk", "cache", "slot", "live", "tile", "top_k", "neuronx_distributed"):
        assert word not in code, word
    family = inspect.getsource(fam)
    assert ".mixer_types" not in family.split("def reference_config")[1]     # the order is the file's, not the program's


def test_the_cell_is_the_issues():
    cell = spec.load_cell(CELL)
    t, e = cell.traffic, cell.traffic["engine"]
    assert cell.chips == 1 and t["kind"] == "open_poisson" and t["service_class"] == "interactive"
    assert t["arrivals"]["cv"] == 1.0 and t["schedule_seed"] == 52 and "sharing" not in t
    assert t["prompt_tokens"] == {"dist": "log_uniform", "low": 8192, "high": 32768} and t["output_tokens"] == 256
    assert t["limits"] == {"ttft_ms": 8000, "tpot_ms": 80, "attainment": 0.9}
    assert t["arrivals"]["rate_rps"] == 0.63                                  # 0.7 x the knee of 0.9 (PERF.md section 4)
    # between the sound readings (1.63 % / 1.56 %; 0) and the variants that have to fail (check_doc)
    assert (t["check"]["tolerance"], t["check"]["cache_tolerance"]) == (0.05, 0.005)
    for word in ("NOT COVERED ON THE CHIP", "1.357 %", "no_selection"):
        assert word in t["check_doc"], word
    assert (t["lead_s"], t["drain_s"], t["trace_s"]) == (10.0, 30.0, 3.0)
    assert (e["lanes"], e["block_size"], e["max_seq_len"]) == (24, 64, 33280)
    assert (e["prefill_chunk_tokens"], e["prefill_buckets"], e["kv_buckets"][-1]) == (512, [128, 512], 33280)
    assert e["block_size"] == cell.config["assumed"]["sparse_config"]["block_size"]      # one pool block one selection block
    assert all(rung % e["block_size"] == 0 for rung in e["kv_buckets"]) and e["kv_buckets"] == sorted(e["kv_buckets"])
    assert e["pool_blocks"] >= e["lanes"] * e["max_seq_len"] // e["block_size"] + 1     # every lane at full length: no preemption
    assert t["prompt_tokens"]["high"] + t["output_tokens"] <= e["max_seq_len"]
    budget = 64 * 64                                                                      # rows inside the budget of blocks
    for traffic in (t, cell.for_rehearsal().traffic):
        sizes, test = traffic["engine"], traffic["check"]
        chunk, n = sizes["prefill_chunk_tokens"], test["prompt_tokens"]
        pieces = [min(chunk, n - at) for at in range(0, n, chunk)]
        # the check passes no length: every piece a whole rung; and a later piece reads a carried state
        assert len(pieces) > 1 and set(pieces) <= set(sizes["prefill_buckets"]), pieces
    rows = t["check"]["prompt_tokens"] + t["check"]["decode_steps"]
    assert (rows - budget) / rows >= 0.55                       # check.py judges medians: most rows on the selection path
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 17 and sum(n.startswith("sala_") for n in names) == 2
    assert {"device_idle_share", "idle_in_step_share", "idle_between_steps_share", "pdecode_dev_p50_ms"} <= set(names)
    bench = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    assert len(bench["per_layer"]) <= 128 and len(bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


@pytest.mark.parametrize("context", [1, 64, 65, 2048, 4096, 4097, 9216, 17700, 33279])
def test_needed_work_is_the_programs_count(context):
    """The benchmark's count of the rows a decode step needs is the program's
    own (``SalaDecode.selected_rows``), and bounded whatever the context."""
    from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
    from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import SALA_CONFIGS

    rows, _ = decode_model_for(SALA_CONFIGS["minicpm-sala"]).selected_rows(context)
    kernels = max((context - 32) // 16 + 1, 0)
    assert arith_sala.selected_blocks(context, 64, 64) == min((context - 1) // 64 + 1, 64)
    assert arith_sala.sparse_decode_needed_bytes(context, 2, 2, 128, 16, 32, 64, 64) == 2 * (
        kernels * 512 + 2 * rows * 512)
    assert rows <= 4096 and (rows == context or context > 4096)


def test_needed_flops_of_a_chunk():
    assert arith_sala.lightning_chunk_flops(0, 0, 6, 32, 128) == 0.0
    per_row = 2 * 128 * 513 + 4 * 128 * 128
    assert arith_sala.lightning_chunk_flops(1024, 2, 6, 32, 128) == 6 * 32 * 1024 * per_row
    # a sliver of the chunk's 2.27 TFLOP of matmul: the roofline reads what the rest costs
    assert arith_sala.lightning_chunk_flops(512, 1, 6, 32, 128) < 0.01 * 2.27e12
