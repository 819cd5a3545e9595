"""Sarvam (MLA + sigmoid-bias MoE with a shared expert, a leading dense layer,
YaRN) at the tiny size, float32 on the CPU: the training-side model against
the benchmark's plain reference, the absorbed form against the expanded one,
the router's contract, the YaRN tables against a direct float64 evaluation,
and the held share of the experts."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec
from neuronx_distributed_llama3_2_tpu.models.mixtral import MIXTRAL_CONFIGS
from neuronx_distributed_llama3_2_tpu.models.olmoe import OLMOE_CONFIGS
from neuronx_distributed_llama3_2_tpu.models.sarvam import (
    QUERY_BLOCK, SARVAM_CONFIGS, LatentAttention, SarvamForCausalLM, absorbed_is_cheaper,
    yarn_mscale, yarn_rope,
)
from neuronx_distributed_llama3_2_tpu.moe.experts import ExpertMLPs
from neuronx_distributed_llama3_2_tpu.moe.model import MoE
from neuronx_distributed_llama3_2_tpu.moe.routing import sigmoid_bias_routing

TINY = SARVAM_CONFIGS["tiny-sarvam"]
TOL = 1e-4


def perturbed(params, seed=4):
    """Every leaf moved off its initial value (norm scales start at one, so a
    model that dropped one would not show otherwise)."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("sarvam")


@pytest.fixture(scope="module")
def params():
    return perturbed(jax.jit(SarvamForCausalLM(TINY).init)(jax.random.key(0)))


def ids_of(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, TINY.vocab_size, shape), jnp.int32)


@pytest.mark.parametrize("held", [(None, 0), (2, 0), (2, 6), (4, 2)], ids=["all", "2@0", "2@6", "4@2"])
def test_apply_matches_the_plain_reference(fam, params, held):
    cfg = dataclasses.replace(TINY, experts_held=held[0], first_held_expert=held[1])
    count = held[0] or TINY.num_experts
    mine = jax.tree.map(lambda a: a, params)
    experts = params["layers"]["moe"]["experts"]
    mine["layers"]["moe"]["experts"] = jax.tree.map(lambda a: a[:, held[1]:held[1] + count], experts)
    ids = ids_of((2, 40))
    got = jax.jit(SarvamForCausalLM(cfg).__call__)(mine, ids)
    with jax.default_matmul_precision("highest"):
        want = fam.reference.forward_logits(mine, fam.reference_config(cfg), ids)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


@pytest.mark.parametrize("t", [1, 7, 24])
def test_absorbed_form_equals_expanded_form(params, t):
    lp = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.key(t), (2, t, TINY.hidden_size))
    sin, cos = SarvamForCausalLM(TINY)._rope(64)
    pos = 5 + jnp.broadcast_to(jnp.arange(t), (2, t))
    attn = LatentAttention(TINY)
    expanded = attn(lp, x, sin, cos, pos - 5, absorbed=False)
    absorbed = attn(lp, x, sin, cos, pos - 5, absorbed=True)
    np.testing.assert_allclose(absorbed, expanded, rtol=TOL, atol=1e-6)


def test_attention_in_query_blocks_equals_one_block(params, monkeypatch):
    from neuronx_distributed_llama3_2_tpu.models import sarvam

    lp = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.key(9), (1, 21, TINY.hidden_size))
    sin, cos = SarvamForCausalLM(TINY)._rope(32)
    pos = jnp.arange(21)[None]
    whole = LatentAttention(TINY)(lp, x, sin, cos, pos)
    monkeypatch.setattr(sarvam, "QUERY_BLOCK", 8)      # 21 rows: 3 blocks, the last padded
    blocked = LatentAttention(TINY)(lp, x, sin, cos, pos)
    np.testing.assert_allclose(blocked, whole, rtol=TOL, atol=1e-6)
    assert QUERY_BLOCK == 512


def test_the_forms_cross_where_the_flops_do():
    big = SARVAM_CONFIGS["sarvam-105b"]
    assert absorbed_is_cheaper(big, 1) and absorbed_is_cheaper(big, 128)
    assert not absorbed_is_cheaper(big, 512) and not absorbed_is_cheaper(big, 3072)


# -- the router ---------------------------------------------------------------

@pytest.fixture(scope="module")
def routed():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(scale=0.5, size=(16,)), jnp.float32)
    return logits, bias


def test_router_bias_changes_the_choice_and_never_a_gate(routed):
    logits, bias = routed
    gates, idx = sigmoid_bias_routing(logits, bias, 4, 2.5)
    plain_gates, plain_idx = sigmoid_bias_routing(logits, jnp.zeros_like(bias), 4, 2.5)
    assert (np.sort(idx, -1) != np.sort(plain_idx, -1)).any()
    # the gates are the chosen experts' unbiased scores, renormalised and scaled
    scores = jax.nn.sigmoid(logits)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(gates, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # where the bias did not change the choice, it did not change a gate either
    same = (np.sort(idx, -1) == np.sort(plain_idx, -1)).all(-1)
    assert same.any()
    np.testing.assert_allclose(np.sort(gates[same], -1), np.sort(plain_gates[same], -1), rtol=1e-6)


def test_router_gates_sum_to_the_scale(routed):
    gates, _ = sigmoid_bias_routing(*routed, 4, 2.5)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-6)


def test_router_takes_the_largest_of_score_plus_bias_and_no_ninth(routed):
    logits, bias = routed
    gates, idx = sigmoid_bias_routing(logits, bias, 4, 2.5)
    order = np.argsort(-(np.asarray(jax.nn.sigmoid(logits)) + np.asarray(bias)), axis=-1)
    assert (np.sort(idx, -1) == np.sort(order[:, :4], -1)).all()
    assert gates.shape == (64, 4) and (idx != order[:, 4:5]).all()


# -- YaRN -------------------------------------------------------------------------

def yarn_float64(position, dim, theta, yarn):
    factor, original, beta_fast, beta_slow, mscale, mscale_all = yarn
    out = []
    low = max(math.floor(dim * math.log(original / (beta_fast * 2 * math.pi)) / (2 * math.log(theta))), 0)
    high = min(math.ceil(dim * math.log(original / (beta_slow * 2 * math.pi)) / (2 * math.log(theta))), dim - 1)
    for i in range(dim // 2):
        freq = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(position * (freq / factor * ramp + freq * (1 - ramp)))
    angles = np.asarray(out + out, np.float64)
    m = (0.1 * mscale * math.log(factor) + 1) / (0.1 * mscale_all * math.log(factor) + 1)
    return np.sin(angles) * m, np.cos(angles) * m


@pytest.mark.parametrize("position", [0, 4095, 4096, 131071])
def test_yarn_tables_against_float64(position):
    big = SARVAM_CONFIGS["sarvam-105b"]
    # one row of the table, computed as the table computes it (float32 angles)
    sin, cos = yarn_rope(big.qk_rope_head_dim, 131072, big.rope_theta, big.yarn)
    want_sin, want_cos = yarn_float64(position, big.qk_rope_head_dim, big.rope_theta, big.yarn)
    # float32 angles: a position of 1e5 times a frequency near one carries an
    # absolute error of about 1e5 * 2^-24 radians
    tol = max(TOL, position * 2.0 ** -23)
    np.testing.assert_allclose(sin[position], want_sin, atol=tol)
    np.testing.assert_allclose(cos[position], want_cos, atol=tol)


def test_yarn_softmax_scale():
    big = SARVAM_CONFIGS["sarvam-105b"]
    assert yarn_mscale(40.0, 1.0) == pytest.approx(1.36889, abs=1e-5)
    assert big.softmax_scale() == pytest.approx(192 ** -0.5 * 1.8739, rel=1e-4)


# -- the share of the experts -----------------------------------------------------------

def test_the_four_shares_sum_to_the_uncut_layer(params):
    """Σ_r (y_r − E_shared(x)) + E_shared(x) is the uncut layer's output."""
    moe_params = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(2), (2, 12, TINY.hidden_size))
    whole_cfg = TINY.moe_config()
    whole, _, idx = MoE(whole_cfg)(moe_params, x)
    shared = MoE(whole_cfg)._shared(moe_params["shared"], x.reshape(-1, TINY.hidden_size)).reshape(x.shape)
    total = shared
    for r in range(4):
        cfg = dataclasses.replace(whole_cfg, experts_held=2, first_held=2 * r)
        part = dict(moe_params, experts=jax.tree.map(lambda a: a[2 * r:2 * r + 2], moe_params["experts"]))
        y_r, _, idx_r = MoE(cfg)(part, x)
        assert (idx_r == idx).all()          # the full router on every rank
        total = total + (y_r - shared)
    np.testing.assert_allclose(total, whole, rtol=TOL, atol=1e-6)


@pytest.mark.parametrize("preset", ["tiny-moe", "tiny-olmoe"])
def test_all_held_first_zero_is_todays_expert_block_bit_for_bit(preset):
    cfg = {**MIXTRAL_CONFIGS, **OLMOE_CONFIGS}[preset].moe_config()
    assert cfg.held == cfg.num_experts and cfg.first_held == 0
    plain = ExpertMLPs(cfg.num_experts, cfg.hidden_size, cfg.intermediate_size, dtype=cfg.dtype)
    held = MoE(cfg)._experts()
    assert held.routed_experts == cfg.num_experts and held.first_expert == 0
    params = plain.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (6, cfg.hidden_size), cfg.dtype)
    logits = jax.random.normal(jax.random.key(2), (6, cfg.num_experts))
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits), cfg.top_k)
    assert (plain(params, x, gates, idx) == held(params, x, gates, idx)).all()
    same = lambda e: str(jax.make_jaxpr(e.forward_all_experts)(params, x, gates, idx))  # noqa: E731
    assert same(plain) == same(held)


def test_a_held_share_refuses_the_capacity_dispatch_and_a_range_outside_the_router():
    cfg = dataclasses.replace(TINY.moe_config(), experts_held=2, capacity_factor=2.0)
    moe = MoE(cfg)
    params = moe.init(jax.random.key(0))
    with pytest.raises(NotImplementedError, match="no-drop path only"):
        moe(params, jnp.zeros((1, 4, TINY.hidden_size)))
    with pytest.raises(ValueError, match="not among the router's"):
        dataclasses.replace(TINY.moe_config(), experts_held=4, first_held=6)
