"""The plain sarvam reference against the program's own training model at the
tiny size: same weights, same tokens, float32 on the CPU — and the ways of
getting this model wrong that the same tolerance has to tell apart."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arith_mla, spec

RTOL = ATOL = 1e-4


@pytest.fixture(scope="module")
def case():
    fam = spec.load_family("sarvam")
    with open(os.path.join(spec.HERE, "configs", "sarvam-105b-ep4-1chip.json")) as f:
        cfg = json.load(f)
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert model_cfg.experts_held == 2 and model_cfg.num_experts == 8 and model_cfg.first_k_dense == 1
    params = jax.jit(fam.train_model(model_cfg).init)(jax.random.key(3))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)
    ])
    ids = jnp.asarray(np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 48)), jnp.int32)
    ref_cfg = fam.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids))
        want_loss = float(jax.jit(lambda p, i: fam.reference.loss(p, ref_cfg, i))(params, ids))
    return fam, model_cfg, params, ids, want, want_loss


def program(fam, model_cfg, params, ids):
    model = fam.train_model(model_cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(model.__call__)(params, ids)), float(jax.jit(model.loss)(params, ids, ids))


def test_reference_matches_the_programs_model(case):
    fam, model_cfg, params, ids, want, want_loss = case
    got, got_loss = program(fam, model_cfg, params, ids)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    assert want_loss <= got_loss + 1e-4 and abs(got_loss - want_loss) < 0.05 * abs(want_loss)


def test_the_margin_is_the_gap_of_score_plus_bias(case):
    fam, model_cfg, params, ids, want, _ = case
    ref_cfg = fam.reference_config(model_cfg)
    logits, margin = jax.jit(lambda p, i: fam.reference.forward_with_margin(p, ref_cfg, i))(params, ids)
    assert margin.shape == ids.shape and float(margin.min()) >= 0.0 and float(margin.max()) <= 1.0
    np.testing.assert_allclose(np.asarray(logits), want, rtol=1e-6, atol=1e-6)


def test_the_published_file_keeps_every_width_and_states_the_cut(case):
    fam = case[0]
    with open(os.path.join(spec.HERE, "configs", "sarvam-105b-ep4-1chip.json")) as f:
        cfg = json.load(f)
    big = fam.model_config(cfg, rehearsal=False, max_seq_len=3072)
    assert (big.hidden_size, big.num_heads, big.qk_nope_head_dim, big.qk_rope_head_dim, big.v_head_dim) == (4096, 64, 128, 64, 128)
    assert (big.kv_lora_rank, big.moe_intermediate_size, big.intermediate_size) == (512, 2048, 16384)
    assert (big.num_experts, big.top_k, big.routed_scaling_factor, big.experts_held) == (128, 8, 2.5, 32)
    assert (big.num_layers, big.first_k_dense, big.vocab_size) == (5, 1, 65536)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {"score_function", "norm_topk_prob", "n_group", "use_qk_norm", "head_dim"} <= set(cfg["assumed"])


def bias_in_the_gate(monkeypatch):
    from neuronx_distributed_llama3_2_tpu.moe import model as moe_model

    def wrong(logits, bias, top_k, scale=1.0, normalize=True):
        biased, idx = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, top_k)
        return scale * biased / biased.sum(-1, keepdims=True), idx.astype(jnp.int32)

    monkeypatch.setattr(moe_model, "sigmoid_bias_routing", wrong)


def latent_norm_skipped(monkeypatch):
    from neuronx_distributed_llama3_2_tpu.models import sarvam

    class NoNorm:
        def __init__(self, *a):
            pass

        def __call__(self, params, x):
            return x

    monkeypatch.setattr(sarvam, "RMSNorm", NoNorm)


WRONG = {
    "gates_not_renormalised": lambda c: dataclasses.replace(c, normalize_top_k=False),
    "scale_dropped": lambda c: dataclasses.replace(c, routed_scaling_factor=1.0),
    "last_expert_dropped": lambda c: dataclasses.replace(c, top_k=c.top_k - 1),
    "another_ranks_share": lambda c: dataclasses.replace(c, first_held_expert=2),
    "no_yarn": lambda c: dataclasses.replace(c, yarn=None),
    "bias_in_the_gate": lambda c: c,
    "latent_norm_skipped": lambda c: c,
}


@pytest.mark.parametrize("mistake", sorted(WRONG))
def test_a_wrong_sarvam_exceeds_the_tolerance(case, mistake, monkeypatch):
    fam, model_cfg, params, ids, want, _ = case
    if mistake in ("bias_in_the_gate", "latent_norm_skipped"):
        globals()[mistake](monkeypatch)
    got, _ = program(fam, WRONG[mistake](model_cfg), params, ids)
    excess = np.abs(got - want) - (ATOL + RTOL * np.abs(want))
    assert (excess > 0).mean() > 0.5, f"{mistake}: only {(excess > 0).mean():.1%} of logits differ"
    assert np.abs(got - want).max() > 50 * ATOL


def test_the_benchmarks_copies_of_the_programs_rules_agree():
    from neuronx_distributed_llama3_2_tpu.models import sarvam

    big = sarvam.SARVAM_CONFIGS["sarvam-105b"]
    assert arith_mla.QUERY_BLOCK == sarvam.QUERY_BLOCK
    for t in (1, 8, 128, 170, 171, 172, 512, 3072):
        assert arith_mla.absorbed_is_cheaper(t, 512, 128, 64, 128) == sarvam.absorbed_is_cheaper(big, t)
    assert arith_mla.latent_row_bytes(512, 64) == 1152
    # a 512-row chunk over 3,072 cached rows, expanded: W_UKV over the rows, then scores and values by head
    want = 2 * 3072 * 512 * 64 * 256 + 2 * 512 * 3072 * 64 * (192 + 128)
    assert arith_mla.prefill_attention_flops(512, 3072, 64, 512, 128, 64, 128) == want
    # a 128-row chunk runs absorbed: multi-query over the 576-wide rows, values their first 512
    assert arith_mla.prefill_attention_flops(128, 3072, 64, 512, 128, 64, 128) == 2 * 128 * 3072 * 64 * (576 + 512)
    # pctx of 3,072: six query blocks against the fresh rows
    assert arith_mla.prefill_attention_flops(3072, 0, 64, 512, 128, 64, 128) == (
        2 * 3072 * 512 * 64 * 256 + 2 * 3072 * 3072 * 64 * 320)
