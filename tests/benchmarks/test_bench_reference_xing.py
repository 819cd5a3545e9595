"""The plain Xing4.0 reference against the program's own training model at
the tiny size: same weights, same tokens, float32 on the CPU — the ways of
getting this model wrong that the same tolerance has to tell apart, and what
the configuration file and the family module hold."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec

RTOL = ATOL = 1e-4
CONFIG = os.path.join(spec.HERE, "configs", "xing4-29b-a4b-1chip.json")
PUBLISHED = {      # the catalog row's `config`, every key
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
    "max_position_embeddings": 262144, "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}


@pytest.fixture(scope="module")
def case():
    fam = spec.load_family("xing")
    with open(CONFIG) as f:
        cfg = json.load(f)
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert (model_cfg.hc_mult, model_cfg.q_lora_rank, model_cfg.first_k_dense) == (4, 24, 1)

    def perturbed(key):
        """Every leaf moved off its initial value (norm scales and the
        residual's gates start at one, its biases at zero or 2 I)."""
        leaves, tree = jax.tree.flatten(fam.train_model(model_cfg).init(key))
        keys = jax.random.split(jax.random.key(4), len(leaves))
        return jax.tree.unflatten(tree, [
            p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])

    params = jax.jit(perturbed)(jax.random.key(3))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 48)), jnp.int32)
    ref_cfg = fam.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids))
    return fam, model_cfg, params, ids, want


def program(fam, model_cfg, params, ids):
    model = fam.train_model(model_cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: model(p, i))(params, ids))


def test_reference_matches_the_programs_model(case):
    fam, model_cfg, params, ids, want = case
    np.testing.assert_allclose(program(fam, model_cfg, params, ids), want, rtol=RTOL, atol=ATOL)


def test_the_margin_is_the_gap_at_the_last_expert_taken(case):
    fam, model_cfg, params, ids, want = case
    ref_cfg = fam.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        logits, margin = jax.jit(lambda p, i: fam.reference.forward_with_margin(p, ref_cfg, i))(params, ids)
    assert margin.shape == ids.shape and float(margin.min()) >= 0.0 and float(margin.max()) <= 1.0
    np.testing.assert_allclose(np.asarray(logits), want, rtol=1e-6, atol=1e-6)


def test_the_rows_kept_are_the_whole_sequences_rows(case):
    """``rows`` keeps the head to some positions (``tools/check_long_rows.py``):
    the same logits and margins as those rows of the whole result."""
    fam, model_cfg, params, ids, want = case
    ref_cfg = fam.reference_config(model_cfg)
    rows = jnp.asarray([0, 7, 31, 32, 47], jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, i: fam.reference.forward_with_margin(p, ref_cfg, i))(params, ids)
        kept = jax.jit(lambda p, i, r: fam.reference.forward_with_margin(p, ref_cfg, i, r))(params, ids, rows)
    assert kept[0].shape == (2, 5, model_cfg.vocab_size) and kept[1].shape == (2, 5)
    np.testing.assert_allclose(np.asarray(kept[0]), np.asarray(whole[0])[:, np.asarray(rows)], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(kept[1]), np.asarray(whole[1])[:, np.asarray(rows)])


def test_the_reference_is_causal_float32_and_takes_nothing_from_the_program(case):
    fam, model_cfg, params, ids, want = case
    ref_cfg = fam.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        short = jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids[:, :20])
    assert short.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(short), want[:, :20], rtol=1e-5, atol=1e-5)
    with open(os.path.join(spec.HERE, "reference", "xing.py")) as f:
        source = f.read()
    assert "neuronx_distributed" not in source and "sigmoid_bias_routing" not in source
    assert "import" in source and "models" not in source.split('"""', 2)[2]


def test_the_published_file_keeps_every_catalog_key_and_states_the_cut(case):
    fam = case[0]
    with open(CONFIG) as f:
        cfg = json.load(f)
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 40 and cfg["num_hidden_layers"] == 5
    big = fam.model_config(cfg, rehearsal=False, max_seq_len=16384)
    assert (big.hidden_size, big.intermediate_size, big.moe_intermediate_size) == (3584, 9216, 1024)
    assert (big.num_heads, big.head_dim, big.v_head_dim, big.q_lora_rank, big.kv_lora_rank) == (32, 192, 128, 768, 512)
    assert (big.num_experts, big.top_k, big.num_shared_experts, big.routed_scaling_factor) == (64, 4, 1, 2.0)
    assert (big.num_layers, big.first_k_dense, big.vocab_size, big.experts_held) == (5, 1, 131072, None)
    assert (big.hc_mult, big.hc_sinkhorn_iters, big.hc_eps, big.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert big.yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0) and big.max_seq_len == 16384
    assert big.cache_row_width == 576 and big.residual_row_bytes == 28672
    # each item of the residual's parameterisation is a key of its own
    assert {"mhc_stream_norm", "mhc_maps", "mhc_sinkhorn_order", "mhc_eps_placement",
            "mhc_streams_in_out", "mhc_update", "mhc_coefficient_dtype",
            "mhc_parameters_per_sublayer", "mhc_seeded_init", "rope_scaling_type",
            "rotary_pairing", "latent_norms", "selection_bias_init", "torch_dtype"} <= set(cfg["assumed"])
    assert set(cfg["not_served"]) == {"num_nextn_predict_layers"} and "deployment" in cfg
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        row = next(c for c in json.load(f)["configs"] if c["name"] == "xing4-29b-a4b-1chip")
    assert row["reduced"] == ["num_hidden_layers"] and row["source"].startswith(cfg["source"])
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200


@pytest.mark.parametrize("key, value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 8), ("attention_bias", True)])
def test_the_family_refuses_a_file_that_states_another_model(case, key, value):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg[key] = value
    with pytest.raises(AssertionError):
        case[0].model_config(cfg, rehearsal=False)


def one_stream(monkeypatch):
    """The residual collapsed to the plain one: ``x + F(norm(x))`` on the
    streams' mean (what a port that ignored ``hc_mult`` would run)."""
    from neuronx_distributed_llama3_2_tpu.models import xing

    def around(self, params, x, sublayer):
        mean = jnp.mean(x, axis=2)
        y, extra = sublayer(mean)
        return x + y[:, :, None, :], extra

    monkeypatch.setattr(xing.HyperConnection, "around", around)


def columns_before_rows(monkeypatch):
    from neuronx_distributed_llama3_2_tpu.models import xing

    def sinkhorn(m, rounds, eps):
        for _ in range(rounds):
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m

    monkeypatch.setattr(xing, "sinkhorn", sinkhorn)


def streams_read_as_a_mean(monkeypatch):
    from neuronx_distributed_llama3_2_tpu.models import xing

    monkeypatch.setattr(xing, "leave_streams", lambda x: jnp.mean(x, axis=2))


WRONG = {
    "scale_dropped": lambda c: dataclasses.replace(c, routed_scaling_factor=1.0),
    "last_expert_dropped": lambda c: dataclasses.replace(c, top_k=c.top_k - 1),
    "no_yarn": lambda c: dataclasses.replace(c, yarn=None),
    "yarn_factor_of_sarvam": lambda c: dataclasses.replace(c, yarn=(4.0,) + c.yarn[1:]),
    "five_rounds": lambda c: dataclasses.replace(c, hc_sinkhorn_iters=5),
    "clamp_at_one": lambda c: dataclasses.replace(c, hc_res_clamp=(-1.0, 1.0)),
    "one_stream": lambda c: c,
}


@pytest.mark.parametrize("mistake", sorted(WRONG))
def test_a_wrong_xing_exceeds_the_tolerance(case, mistake, monkeypatch):
    fam, model_cfg, params, ids, want = case
    if mistake in globals():
        globals()[mistake](monkeypatch)
    got = program(fam, WRONG[mistake](model_cfg), params, ids)
    excess = np.abs(got - want) - (ATOL + RTOL * np.abs(want))
    assert (excess > 0).mean() > 0.5, f"{mistake}: only {(excess > 0).mean():.1%} of logits differ"
    assert np.abs(got - want).max() > 50 * ATOL


@pytest.mark.parametrize("mistake", ["streams_read_as_a_mean", "columns_before_rows"])
def test_two_mistakes_the_logits_cannot_see(case, mistake, monkeypatch):
    """Noted, not hidden: the final RMSNorm divides a constant factor out, so
    a mean for the streams' sum reads the same; and after 20 rounds the mix is
    doubly stochastic within 1e-5 for the typical token whichever of rows and
    columns a round takes first."""
    fam, model_cfg, params, ids, want = case
    globals()[mistake](monkeypatch)
    np.testing.assert_allclose(program(fam, model_cfg, params, ids), want, rtol=1e-2, atol=1e-2)
