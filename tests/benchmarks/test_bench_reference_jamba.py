"""The Jamba configuration as the benchmark runs it: the file against the
catalog's published keys (nothing cut), the family module (file ->
``JambaConfig``), the plain reference against the program's training model at
the rehearsal's size, the benchmark's byte arithmetic against the program's
own shapes, and the cell's sizes."""

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arith_ssm, spec

RTOL = ATOL = 1e-4
PUBLISHED = {      # the catalog row's `config`, every key
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536,
}
CELL = "jamba-smallchat-bursty"


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("jamba")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(spec.HERE, "configs", "jamba2-3b-1chip.json")) as f:
        return json.load(f)


def test_the_file_has_every_published_key_and_cuts_nothing(cfg):
    assert {k for k, v in PUBLISHED.items() if cfg[k] != v} == set() == set(cfg["reduced"])
    bench = json.load(open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")))
    row = next(c for c in bench["configs"] if c["name"] == "jamba2-3b-1chip")
    assert row["reduced"] == [] and row["source"] == cfg["source"]
    for key in ("layer_order", "dense_feed_forward", "head_dim", "no_positional_term", "inner_norms",
                "state_dtype", "compute_dtype", "initialisation", "hf_tensor_names", "torch_dtype"):
        assert len(cfg["assumed"][key]) > 40, key          # each with its argument
    assert cfg["chips"] == 1 and cfg["layout"] == {"tp": 1} and len(cfg["deployment"]) > 40


def test_the_family_builds_the_programs_config_at_published_widths(fam, cfg):
    c = fam.model_config(cfg, rehearsal=False, max_seq_len=2304)
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (2560, 20, 1, 128)
    assert (c.intermediate_size, c.vocab_size, c.num_layers, c.max_seq_len) == (8192, 65536, 28, 2304)
    assert (c.d_inner, c.mamba_d_state, c.mamba_d_conv, c.mamba_dt_rank) == (5120, 16, 4, 160)
    assert c.rms_norm_eps == 1e-6 and c.tie_word_embeddings and c.dtype == jnp.bfloat16
    assert [i for i, k in enumerate(c.layer_kinds) if k == "attention"] == [7, 21]
    # a lane's state as the file states it, the program counts it and the benchmark's arithmetic has it
    assert cfg["state_bytes_per_lane"] == 26 * c.state_bytes_per_layer() == 26 * arith_ssm.state_bytes(5120, 16, 4)
    ref = fam.reference_config(c)
    assert ref["layer_kinds"].count("mamba") == 26 and ref["num_key_value_heads"] == 1
    shapes = jax.eval_shape(fam.train_model(c).init, jax.random.key(0))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["mamba_layers"]["mamba"]) == 26 * arith_ssm.mamba_layer_params(2560, 5120, 16, 4, 160)
    # every weight once a decode step: all of them, the tied embedding as the head
    assert arith_ssm.decode_weight_bytes(
        2560, 20, 1, 128, 8192, 65536, 26, 2, 5120, 16, 4, 160) == 2 * (count(shapes) - 2560)   # less the final norm
    with pytest.raises(ValueError, match="the file says otherwise"):
        fam.model_config({**cfg, "num_experts": 8}, rehearsal=False)


def test_reference_matches_the_programs_model_at_the_rehearsals_size(fam, cfg):
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert model_cfg.num_layers == 5 and model_cfg.layer_kinds.count("attention") == 2
    params = jax.jit(fam.train_model(model_cfg).init)(jax.random.key(3))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])
    ids = jnp.asarray(np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 48)), jnp.int32)
    ref_cfg = fam.reference_config(model_cfg)
    model = fam.train_model(model_cfg)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids)
        want_loss = float(jax.jit(lambda p, i: fam.reference.loss(p, ref_cfg, i))(params, ids))
        got, got_loss = jax.jit(model.__call__)(params, ids), float(jax.jit(model.loss)(params, ids, ids))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    assert abs(got_loss - want_loss) < 1e-4 * abs(want_loss)


def test_the_reference_is_one_sequence_from_its_first_row_alone(fam):
    code = inspect.getsource(fam.reference).split('"""', 2)[2]
    for word in ("tail", "chunk", "cache", "slot", "live", "rope", "rotary", "neuronx_distributed"):
        assert word not in code, word


def test_the_cell_is_the_issues():
    cell = spec.load_cell(CELL)
    t, e = cell.traffic, cell.traffic["engine"]
    assert cell.chips == 1 and t["kind"] == "open_poisson" and t["service_class"] == "interactive"
    assert t["arrivals"]["cv"] == 2.0 and t["schedule_seed"] == 49 and "sharing" not in t
    assert t["prompt_tokens"] == {"dist": "log_uniform", "low": 64, "high": 2048} and t["output_tokens"] == 256
    assert t["limits"] == {"ttft_ms": 1000, "tpot_ms": 60, "attainment": 0.9}
    assert (t["lead_s"], t["drain_s"], t["trace_s"]) == (10.0, 30.0, 3.0)
    assert e == {"lanes": 128, "block_size": 16, "max_seq_len": 2304, "pool_blocks": 18560,
                 "prefill_chunk_tokens": 512, "prefill_buckets": [128, 512], "kv_buckets": [2304]}
    assert e["pool_blocks"] >= e["lanes"] * e["max_seq_len"] // e["block_size"] + 1     # the pool never runs out
    assert t["prompt_tokens"]["high"] + t["output_tokens"] == e["max_seq_len"]
    for traffic in (t, cell.for_rehearsal().traffic):
        sizes, test = traffic["engine"], traffic["check"]
        chunk, n = sizes["prefill_chunk_tokens"], test["prompt_tokens"]
        pieces = [min(chunk, n - at) for at in range(0, n, chunk)]
        # the check passes no length: every piece a whole rung; and a later piece reads a carried state
        assert len(pieces) > 1 and set(pieces) <= set(sizes["prefill_buckets"]), pieces
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 20 and sum(n.startswith("ssm_") for n in names) == 5
    assert {"device_idle_share", "idle_in_step_share", "idle_between_steps_share"} <= set(names)
    assert not {"moe_dev_share", "kv_dev_share", "prefix_hit_rate", "gen_state_bytes_per_lane"} & set(names)


def test_needed_bytes():
    assert arith_ssm.state_bytes(5120, 16, 4) == 327_680 + 30_720
    assert arith_ssm.decode_needed_state_bytes(128, 26, 5120, 16, 4) == 128 * 26 * 2 * 358_400
    row = arith_ssm.prefill_row_bytes(5120, 16)
    assert row == 5120 * (2 + 2) + 5120 * (2 + 4 + 4) + 2 * 16 * 4
    assert arith_ssm.prefill_needed_bytes(900, 2, 26, 5120, 16, 4) == 26 * (900 * row + 2 * 2 * 358_400)
