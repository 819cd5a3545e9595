"""The Brumby configuration as the benchmark runs it: the file against the
catalog's published keys and the program's own arithmetic, the family module
(file -> ``BrumbyConfig``), the plain reference against the program's training
model at the rehearsal's size, and the cell's sizes (one state a block)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arith_retention, spec

RTOL = ATOL = 1e-4
PUBLISHED = {      # the catalog row's `config`, every key
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("brumby")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(spec.HERE, "configs", "brumby-14b-1chip.json")) as f:
        return json.load(f)


def test_the_file_has_every_published_key_and_cuts_depth_alone(cfg):
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 40 and cfg["num_hidden_layers"] == 6
    for key in ("power_degree", "normaliser", "gate", "scale", "qk_norm_and_rotary", "state_dtype",
                "retention_feature_width"):
        assert len(cfg["assumed"][key]) > 40, key          # each with its argument


def test_the_family_builds_the_programs_config_at_published_widths(fam, cfg):
    c = fam.model_config(cfg, rehearsal=False, max_seq_len=8704)
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim) == (5120, 40, 8, 128)
    assert (c.intermediate_size, c.vocab_size, c.num_layers, c.max_seq_len) == (17408, 151936, 6, 8704)
    assert c.rope_theta == 1e6 and c.rms_norm_eps == 1e-6 and not c.tie_word_embeddings
    assert c.dtype == jnp.bfloat16
    # the state's bytes as the file states them, the program counts them and the benchmark's arithmetic has them
    assert str(c.feature_width) in cfg["assumed"]["retention_feature_width"]
    assert cfg["state_bytes_per_layer"] == c.state_bytes_per_layer() == arith_retention.state_bytes(8, 128, 9216)
    ref = fam.reference_config(c)
    assert ref["num_key_value_heads"] == 8 and ref["retention_eps"] == 1e-6 and "feature_width" not in ref
    shapes = jax.eval_shape(fam.train_model(c).init, jax.random.key(0))
    layer = sum(a.size for a in jax.tree.leaves(shapes["layers"])) // 6
    assert 330.2e6 < layer < 330.4e6                          # 330.3 M parameters a layer
    assert arith_retention.decode_weight_bytes(5120, 40, 8, 128, 17408, 151936, 6) == pytest.approx(
        2 * (sum(a.size for a in jax.tree.leaves(shapes)) - shapes["embed"]["embedding"].size), rel=1e-4)


def test_reference_matches_the_programs_model_at_the_rehearsals_size(fam, cfg):
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert model_cfg.num_layers == 2 and model_cfg.head_dim == 32
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


def test_the_reference_is_the_attention_form_alone(fam):
    import inspect

    source = inspect.getsource(fam.reference)
    code = source.split('"""', 2)[2]
    for word in ("power_features", "feature", "state", "chunk", "neuronx_distributed"):
        assert word not in code, word


def test_the_cell_sizes_a_block_as_a_sequence():
    cell = spec.load_cell("brumby-longgen-batch")
    e = cell.traffic["engine"]
    assert e["block_size"] == e["max_seq_len"] == e["kv_buckets"][0] == 8704
    assert e["pool_blocks"] == 1 + e["lanes"] + 2 and cell.traffic["clients"] == 1.5 * e["lanes"]
    assert cell.traffic["prompt_tokens"]["high"] + cell.traffic["output_tokens"] == e["max_seq_len"]
    assert "sharing" not in cell.traffic
    for traffic in (cell.traffic, cell.for_rehearsal().traffic):
        sizes, test = traffic["engine"], traffic["check"]
        chunk, n = sizes["prefill_chunk_tokens"], test["prompt_tokens"]
        pieces = [min(chunk, n - at) for at in range(0, n, chunk)]
        # the check passes no length: every piece a whole rung
        assert set(pieces) <= set(sizes["prefill_buckets"]), pieces
        # the first inner chunk of the first piece starts from the zero state and reads none: most rows
        # have to come after it, or the median row could not tell a state pool in a lower precision
        # from the plain one (a piece longer than the inner chunk carries its state across in the pool's dtype)
        unread = min(pieces[0], arith_retention.CHUNK_ROWS)
        assert n + test["decode_steps"] - unread > (n + test["decode_steps"]) / 2
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 12 and all(n.startswith("gen_") for n in names)
    assert {m["layer"] for m in cell.per_layer} == {"decode programs", "scheduler", "device"}


def test_needed_bytes_and_executed_flops():
    per_lane = arith_retention.decode_needed_state_bytes(1, 6, 8, 128)
    assert per_lane == 6 * 2 * 8 * 8256 * 129 * 4
    whole = arith_retention.chunk_retention_flops(512, 40, 8, 128, 9216)
    inner = arith_retention.CHUNK_ROWS           # a bucket is taken this many rows at a time
    assert whole == 512 // inner * (
        2.0 * inner * inner * 40 * 128 * 2 + 2.0 * inner * 40 * 9216 * 129 + 2.0 * inner * 8 * 9216 * 128)
    assert arith_retention.chunk_retention_flops(8704, 40, 8, 128, 9216) == 17 * whole
    assert arith_retention.chunk_retention_flops(128, 40, 8, 128, 9216) == whole / 4
