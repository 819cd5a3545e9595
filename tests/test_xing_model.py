"""Xing4.0 (latent attention with a query latent under a four-stream,
Sinkhorn-mixed residual) at the tiny size, float32 on the CPU: the
training-side model against the benchmark's plain reference, the absorbed
form against the expanded one with the query latent, what the Sinkhorn rounds
and the clamp give, that the streams are mixed at all and that each part of
the residual is seen by the logits, and the registry."""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arith_residual, spec
from neuronx_distributed_llama3_2_tpu.models import model_registry
from neuronx_distributed_llama3_2_tpu.models.sarvam import LatentAttention
from neuronx_distributed_llama3_2_tpu.models.xing import (
    XING_CONFIGS, HyperConnection, XingConfig, XingForCausalLM, enter_streams, leave_streams,
    sinkhorn,
)

TINY = XING_CONFIGS["tiny-xing"]
TOL = 1e-4


def perturbed(params, seed=4):
    """Every leaf moved off its initial value (norm scales and the residual's
    gates start at one, its biases at zero: a model that dropped one would not
    show otherwise)."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("xing")


@pytest.fixture(scope="module")
def params():
    return perturbed(jax.jit(XingForCausalLM(TINY).init)(jax.random.key(0)))


def load_tool(name):
    """``benchmarks/tools/<name>.py`` as a module (the tools are scripts)."""
    import importlib.util
    import os

    path = os.path.join(spec.HERE, "tools", name + ".py")
    module_spec = importlib.util.spec_from_file_location(f"benchmarks_tools_{name}", path)
    module = importlib.util.module_from_spec(module_spec)
    saved = sys.path[0]
    try:
        module_spec.loader.exec_module(module)       # the script sets sys.path[0] for itself
    finally:
        sys.path[0] = saved
    return module


def ids_of(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, TINY.vocab_size, shape), jnp.int32)


def reference_logits(fam, cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, i: fam.reference.forward_logits(p, fam.reference_config(cfg), i))(params, ids)


@pytest.mark.parametrize("length", [40, 24], ids=["past-yarn-original", "inside"])
def test_apply_matches_the_plain_reference(fam, params, length):
    """YaRN's original range is 32 positions at this size: 40 rows cross it."""
    assert TINY.yarn[1] == 32 and TINY.hc_mult == 4 and TINY.q_lora_rank == 24
    ids = ids_of((2, length))
    got = jax.jit(XingForCausalLM(TINY).__call__)(params, ids)
    np.testing.assert_allclose(got, reference_logits(fam, TINY, params, ids), rtol=TOL, atol=TOL)


def test_the_reference_in_blocks_equals_the_reference_whole(fam, params, monkeypatch):
    """Query blocks that do not divide the length, head blocks over the
    vocabulary: the same logits as one block of each."""
    ids = ids_of((1, 40), seed=2)
    whole = reference_logits(fam, TINY, params, ids)
    monkeypatch.setattr(fam.reference, "ROW_BLOCK", 16)
    monkeypatch.setattr(fam.reference, "VOCAB_BLOCK", 64)
    np.testing.assert_allclose(reference_logits(fam, TINY, params, ids), whole, rtol=1e-5, atol=1e-5)


def test_the_loss_matches_the_reference(fam, params):
    ids = ids_of((2, 24), seed=3)
    cfg = dataclasses.replace(TINY, router_aux_loss_coef=0.0)
    got = jax.jit(XingForCausalLM(cfg).loss)(params, ids, ids)
    with jax.default_matmul_precision("highest"):
        want = fam.reference.loss(params, fam.reference_config(cfg), ids)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("t", [1, 7, 24])
def test_absorbed_form_equals_expanded_form_with_the_query_latent(params, t):
    lp = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    assert {"q_a", "q_norm", "q_b"} <= set(lp) and "q" not in lp
    x = jax.random.normal(jax.random.key(t), (2, t, TINY.hidden_size))
    sin, cos = XingForCausalLM(TINY)._rope(64)
    pos = jnp.broadcast_to(jnp.arange(t), (2, t))
    attn = LatentAttention(TINY)
    expanded = attn(lp, x, sin, cos, pos, absorbed=False)
    absorbed = attn(lp, x, sin, cos, pos, absorbed=True)
    np.testing.assert_allclose(absorbed, expanded, rtol=TOL, atol=1e-6)


def test_sinkhorn_makes_the_mix_doubly_stochastic_after_20_rounds_and_not_after_1():
    """At the seeded weights' statistics (unit-normal entries + 2 I before the
    exp), over 4,096 tokens: after 20 rounds the typical row sums to 1 within
    1e-5 and the worst within 0.03 (a near-permutation converges slowly);
    after 1 round the worst row is off by more than 0.3. A round ends on the
    columns, which sum to 1 but for ``hc_eps`` beside a small sum."""
    m = jnp.exp(jax.random.normal(jax.random.key(0), (4096, 4, 4)) + 2.0 * jnp.eye(4))
    one, twenty = sinkhorn(m, 1, 1e-6), sinkhorn(m, 20, 1e-6)
    rows_off = lambda h: np.abs(np.asarray(h.sum(-1)) - 1.0)  # noqa: E731
    np.testing.assert_allclose(one.sum(-2), 1.0, atol=1e-4)
    np.testing.assert_allclose(twenty.sum(-2), 1.0, atol=1e-4)
    assert rows_off(twenty).max() < 0.03 and np.median(rows_off(twenty)) < 1e-5
    assert rows_off(one).max() > 0.3 and np.median(rows_off(one)) > 0.03


@pytest.mark.parametrize("value", [1e4, -1e4])
def test_the_clamp_holds_exp_finite_at_huge_inputs(params, value):
    hc = HyperConnection(TINY)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["attn_hc"])
    lp = {**lp, "b_res": jnp.full((4, 4), value, jnp.float32).at[0, 1].set(-value)}
    x = jax.random.normal(jax.random.key(1), (1, 3, 4, TINY.hidden_size))
    pre, post, res = hc.coefficients(lp, x)
    assert bool(jnp.isfinite(res).all()) and float(res.min()) >= 0.0
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-4)
    # without the clamp exp overflows (or underflows to a 0 / 0)
    loose = HyperConnection(dataclasses.replace(TINY, hc_res_clamp=(-1e9, 1e9)))
    assert not bool(jnp.isfinite(loose.coefficients(lp, x)[2]).all())


def test_the_seeded_mix_is_far_from_the_identity_and_from_the_uniform_matrix():
    """What the module text promises of ``HyperConnection.init``: a test that
    mixes nothing or everything would not tell streams apart."""
    model = XingForCausalLM(TINY)
    fresh = jax.jit(model.init)(jax.random.key(0))
    lp = jax.tree.map(lambda a: a[0], fresh["layers"])
    x = enter_streams(TINY, model._embed()(fresh["embed"], ids_of((2, 16))))
    x = x + 0.3 * jax.random.normal(jax.random.key(5), x.shape)        # streams that differ
    pre, post, res = HyperConnection(TINY).coefficients(lp["attn_hc"], x)
    diag = jnp.diagonal(res, axis1=-2, axis2=-1)
    assert 0.35 < float(diag.mean()) < 0.85
    assert float(jnp.std(res[..., 0, 1])) > 0.02             # it moves by token
    assert 0.2 < float(pre.mean()) < 0.8 and 0.4 < float(post.mean()) < 1.6


def test_streams_in_and_out():
    x = jax.random.normal(jax.random.key(0), (2, 3, TINY.hidden_size))
    streams = enter_streams(TINY, x)
    assert streams.shape == (2, 3, 4, TINY.hidden_size)
    np.testing.assert_allclose(leave_streams(streams), 4 * x, rtol=1e-6)


@pytest.mark.parametrize("part", ["phi", "alpha", "b_pre", "b_post", "b_res"])
def test_every_parameter_of_the_residual_moves_the_logits(params, part):
    ids = ids_of((1, 12), seed=7)
    model = jax.jit(XingForCausalLM(TINY).__call__)
    base = model(params, ids)
    moved = jax.tree.map(lambda a: a, params)
    leaf = moved["layers"]["mlp_hc"][part]
    moved["layers"]["mlp_hc"][part] = leaf + 0.5 * jnp.sign(leaf + 1e-9)
    assert float(jnp.max(jnp.abs(model(moved, ids) - base))) > 1e-3


@pytest.mark.parametrize("fault", ["one_round", "identity_res", "no_q_norm"])
def test_a_planted_fault_is_seen_against_the_reference(fam, params, fault, monkeypatch):
    """The faults the chip's check is given (benchmarks/tools/check_residual_variant.py),
    at the tiny size: each moves the logits far past the tolerance."""
    check_residual_variant = load_tool("check_residual_variant")

    ids = ids_of((1, 24), seed=9)
    want = reference_logits(fam, TINY, params, ids)
    name = {"one_round": "one_sinkhorn_round", "identity_res": "identity_res",
            "no_q_norm": "q_latent_no_norm"}[fault]
    # a fresh lambda a call: equal bound methods would share one jit cache entry
    undo = check_residual_variant.plant(name)
    try:
        got = jax.jit(lambda p, i: XingForCausalLM(TINY)(p, i))(params, ids)
    finally:
        undo()
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert float(np.median(err)) > 100 * TOL, (fault, float(np.median(err)))
    sound = jax.jit(lambda p, i: XingForCausalLM(TINY)(p, i))(params, ids)
    np.testing.assert_allclose(sound, want, rtol=TOL, atol=TOL)


def test_the_registry_and_the_published_preset():
    reg = model_registry()
    assert reg["tiny-xing"]["model_cls"] is XingForCausalLM and reg["xing4.0-29b-a4b"]["from_hf"] is None
    big = XING_CONFIGS["xing4.0-29b-a4b"]
    assert isinstance(big, XingConfig) and big.cache_row_width == 576 and big.head_dim == 192
    assert (big.num_layers, big.first_k_dense, big.num_experts, big.top_k) == (40, 2, 64, 4)
    assert big.residual_row_bytes == 28672 == arith_residual.residual_row_bytes(4, 3584)
    assert big.softmax_scale() == pytest.approx(192 ** -0.5 * 2.005, rel=1e-3)


def test_the_byte_counts_at_the_published_widths():
    """ISSUE 44's table: the MLA block 28.4 M, the connections 0.69 M a
    layer, a decode step's weights 7.16 GB."""
    assert arith_residual.attention_params(3584, 32, 768, 512, 128, 64, 128) == pytest.approx(28.4e6, rel=2e-3)
    assert 2 * arith_residual.connection_params(4, 3584) == pytest.approx(0.69e6, rel=5e-3)
    weights = arith_residual.decode_weight_bytes(
        lanes=32, hidden=3584, heads=32, q_rank=768, kv_rank=512, d_nope=128, d_rope=64, d_v=128,
        streams=4, dense_layers=1, dense_width=9216, expert_layers=4, num_experts=64, top_k=4,
        expert_width=1024, shared_width=1024, vocab=131072)
    # everything but the embedding table: 8.10 GB - 0.94 GB
    assert weights == pytest.approx(7.16e9, rel=3e-3)
    # two lanes reach at most 8 of the 64 experts a layer
    fewer = arith_residual.decode_weight_bytes(
        lanes=2, hidden=3584, heads=32, q_rank=768, kv_rank=512, d_nope=128, d_rope=64, d_v=128,
        streams=4, dense_layers=1, dense_width=9216, expert_layers=4, num_experts=64, top_k=4,
        expert_width=1024, shared_width=1024, vocab=131072)
    assert weights - fewer == pytest.approx(4 * 56 * 3 * 3584 * 1024 * 2)
