"""Laguna (window and full attention layers mixed, two head counts, two rotary
tables, per-head output gates, softmax top-k experts scaled + a shared expert,
a leading dense layer) at the tiny size, float32 on the CPU: the training-side
model's loss and gradients against the benchmark's plain reference (its logits:
``tests/benchmarks/test_bench_reference_laguna.py``), a window layer against a
brute-force masked softmax, the rotary tables against ``transformers``'
formulas written out here, the gate, the router's scale, the stacks and the
published lists."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec
from neuronx_distributed_llama3_2_tpu.models import model_registry
from neuronx_distributed_llama3_2_tpu.models.laguna import (
    FULL, LAGUNA_CONFIGS, WINDOW, LagunaAttention, LagunaForCausalLM, layer_runs,
    params_from_hf_laguna, params_to_hf_laguna, rope_tables, stack_sizes,
)
from neuronx_distributed_llama3_2_tpu.models.mixtral import MIXTRAL_CONFIGS
from neuronx_distributed_llama3_2_tpu.moe.model import MoE

TINY = LAGUNA_CONFIGS["tiny-laguna"]
BIG = LAGUNA_CONFIGS["laguna-xs.2"]
TOL = 1e-4


def perturbed(params, seed=4):
    """Every leaf moved off its initial value (norm scales start at one)."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("laguna")


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: perturbed(LagunaForCausalLM(TINY).init(key)))(jax.random.key(0))


def ids_of(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, TINY.vocab_size, shape), jnp.int32)


def test_loss_and_gradients_match_the_plain_reference(fam, params):
    ids = ids_of((2, 24), seed=1)
    model = LagunaForCausalLM(dataclasses.replace(TINY, router_aux_loss_coef=0.0))
    ref_cfg = fam.reference_config(TINY)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(lambda p: model.loss(p, ids, ids)))(params)
        want, g_want = jax.jit(jax.value_and_grad(lambda p: fam.reference.loss(p, ref_cfg, ids)))(params)
    assert abs(float(got) - float(want)) < TOL
    for (path, a), b in zip(jax.tree.leaves_with_path(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=TOL, err_msg=str(path))
    # every stack learns: no layer is skipped by the published order
    for name in stack_sizes(TINY):
        assert float(jnp.abs(g_got[name]["attn"]["out_gate"]["kernel"]).max()) > 0, name


@pytest.mark.parametrize("context", [5, 8, 30], ids=["below", "at", "several-windows"])
@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_a_layer_alone_against_a_brute_force_masked_softmax(params, kind, context):
    attn = LagunaAttention(TINY, kind)
    lp = jax.tree.map(lambda a: a[0], params[f"{kind}_layers"]["attn"])
    h = jax.random.normal(jax.random.key(context), (1, context, TINY.hidden_size), jnp.float32)
    sin, cos = rope_tables(TINY, kind, context)
    positions = jnp.arange(context, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(attn.__call__)(lp, h, sin, cos, positions))[0]
        q, k, v = (np.asarray(a, np.float64)[0] for a in jax.jit(attn.project)(lp, h, sin, cos, positions))
        gate = 1.0 / (1.0 + np.exp(-np.asarray(h[0] @ lp["out_gate"]["kernel"], np.float64)))
    n, g, d = attn.heads, attn.heads // TINY.num_kv_heads, TINY.head_dim
    y = np.zeros((context, n, d))
    for i in range(context):
        first = max(0, i - TINY.sliding_window + 1) if kind == WINDOW else 0
        for m in range(n):
            s = k[first:i + 1, m // g] @ q[i, m] / math.sqrt(d)
            p = np.exp(s - s.max())
            y[i, m] = (p / p.sum()) @ v[first:i + 1, m // g] * gate[i, m]
    want = y.reshape(context, n * d) @ np.asarray(lp["o"]["kernel"], np.float64)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def hf_default_inv_freq(dim, base):
    return 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))


def hf_yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """``transformers`` ``_compute_yarn_parameters`` (``truncate`` on)."""
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor


@pytest.mark.parametrize("cfg", [TINY, BIG], ids=["tiny", "published"])
def test_the_two_rotary_tables_are_transformers(cfg):
    length = 96
    pos = np.arange(length, dtype=np.float64)[:, None]
    # window: rope_type default over the whole head
    sin, cos = rope_tables(cfg, WINDOW, length)
    freqs = pos * hf_default_inv_freq(cfg.head_dim, cfg.window_rope_theta)[None]
    emb = np.concatenate([freqs, freqs], axis=-1)
    assert sin.shape == (length, cfg.head_dim)
    np.testing.assert_allclose(np.asarray(sin), np.sin(emb), atol=2e-5)
    np.testing.assert_allclose(np.asarray(cos), np.cos(emb), atol=2e-5)
    # full: yarn over the first partial_rotary_factor of the head, both tables
    # times attention_factor
    factor, original, beta_fast, beta_slow, attention_factor = cfg.yarn
    dim = int(cfg.head_dim * cfg.partial_rotary_factor)
    sin, cos = rope_tables(cfg, FULL, length)
    freqs = pos * hf_yarn_inv_freq(dim, cfg.rope_theta, factor, original, beta_fast, beta_slow)[None]
    emb = np.concatenate([freqs, freqs], axis=-1)
    assert sin.shape == (length, dim) and dim == cfg.rotary_dim(FULL)
    np.testing.assert_allclose(np.asarray(sin), np.sin(emb) * attention_factor, atol=3e-5)
    np.testing.assert_allclose(np.asarray(cos), np.cos(emb) * attention_factor, atol=3e-5)
    assert attention_factor == pytest.approx(0.1 * math.log(factor) + 1.0, rel=1e-6)


def test_the_unrotated_half_of_a_full_layers_heads_passes_through(params):
    attn = LagunaAttention(TINY, FULL)
    lp = jax.tree.map(lambda a: a[0], params["full_layers"]["attn"])
    h = jax.random.normal(jax.random.key(1), (1, 12, TINY.hidden_size), jnp.float32)
    positions = jnp.arange(12, dtype=jnp.int32)[None]
    sin, cos = rope_tables(TINY, FULL, 12)
    q, k, _ = attn.project(lp, h, sin, cos, positions)
    q0, k0, _ = attn.project(lp, h, jnp.zeros_like(sin), jnp.ones_like(cos), positions)
    r = TINY.rotary_dim(FULL)
    assert r == TINY.head_dim // 2
    np.testing.assert_array_equal(np.asarray(q[..., r:]), np.asarray(q0[..., r:]))
    np.testing.assert_array_equal(np.asarray(k[..., r:]), np.asarray(k0[..., r:]))
    assert float(jnp.abs(q[:, 1:, :, :r] - q0[:, 1:, :, :r]).max()) > 1e-3


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_a_zero_gate_halves_every_heads_output(params, kind):
    attn = LagunaAttention(TINY, kind)
    lp = jax.tree.map(lambda a: a[0], params[f"{kind}_layers"]["attn"])
    h = jax.random.normal(jax.random.key(2), (1, 6, TINY.hidden_size), jnp.float32)
    att = jax.random.normal(jax.random.key(3), (1, 6, attn.heads, TINY.head_dim), jnp.float32)
    zero = {**lp, "out_gate": {"kernel": jnp.zeros_like(lp["out_gate"]["kernel"])}}
    ungated = att.reshape(1, 6, -1) @ lp["o"]["kernel"]
    np.testing.assert_allclose(
        np.asarray(attn.output(zero, h, att)), 0.5 * np.asarray(ungated), rtol=1e-5, atol=1e-6)
    assert lp["out_gate"]["kernel"].shape == (TINY.hidden_size, attn.heads)
    # the gate is a scalar a head: scaling one head's gate column moves that head alone
    assert not np.allclose(np.asarray(attn.output(lp, h, att)), 0.5 * np.asarray(ungated), atol=1e-3)


def test_the_topk_router_applies_the_routed_scale_and_only_where_stated():
    cfg = TINY.moe_config()
    assert (cfg.routed_scale, cfg.routing, cfg.normalize_top_k) == (2.5, "topk", True)
    moe = MoE(cfg)
    p = moe.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 5, TINY.hidden_size), jnp.float32)
    _, gates, idx = moe._route(p["router"], x.reshape(-1, TINY.hidden_size))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
    assert idx.shape == (10, TINY.top_k)
    # a model that states none keeps its program: no multiply by 1 is traced
    plain = MoE(MIXTRAL_CONFIGS["tiny-moe"].moe_config())
    pp = plain.init(jax.random.key(0))
    xs = jnp.zeros((4, MIXTRAL_CONFIGS["tiny-moe"].hidden_size), jnp.float32)
    scaled = MoE(dataclasses.replace(plain.config, routed_scale=2.5))
    count = lambda m: str(jax.make_jaxpr(lambda a: m._route(pp["router"], a)[1])(xs)).count(" mul ")  # noqa: E731
    assert count(scaled) == count(plain) + 1


def test_the_published_lists_become_one_stack_a_shape():
    assert BIG.kinds[:5] == (FULL, WINDOW, WINDOW, WINDOW, FULL)
    assert (BIG.heads_of(FULL), BIG.heads_of(WINDOW), BIG.num_kv_heads) == (48, 64, 8)
    assert (BIG.layers_of(FULL), BIG.layers_of(WINDOW)) == (10, 30)
    assert stack_sizes(BIG) == {
        "full_dense_layers": (FULL, False, 1), "window_layers": (WINDOW, True, 30),
        "full_layers": (FULL, True, 9)}
    runs = layer_runs(BIG)
    assert [r.count for r in runs] == [1] + [3, 1] * 9 + [3]
    assert sum(r.count for r in runs) == 40 and [r.layer for r in runs][:4] == [0, 1, 4, 5]
    # a run's place in its stack and among the layers of its kind (the cache's layer index)
    assert [(r.stack, r.first, r.kind_first) for r in runs[:5]] == [
        ("full_dense_layers", 0, 0), ("window_layers", 0, 0), ("full_layers", 0, 1),
        ("window_layers", 3, 3), ("full_layers", 1, 2)]
    shapes = jax.eval_shape(LagunaForCausalLM(TINY).init, jax.random.key(0))
    assert shapes["window_layers"]["attn"]["qkv"]["q_kernel"].shape == (3, 64, 6 * 16)
    assert shapes["full_layers"]["attn"]["qkv"]["q_kernel"].shape == (1, 64, 4 * 16)
    assert shapes["full_dense_layers"]["mlp"]["gate_up"].shape == (1, 64, 2, 128)
    # `router` names the router's kernel and no other leaf (check.py sharpens by that name)
    with_router = [jax.tree_util.keystr(path) for path, _ in jax.tree.leaves_with_path(shapes)
                   if "router" in jax.tree_util.keystr(path)]
    assert sorted(with_router) == [
        "['full_layers']['moe']['router']['kernel']", "['window_layers']['moe']['router']['kernel']"]


@pytest.mark.parametrize("wrong, match", [
    (dict(layer_types=TINY.layer_types[:4]), "entries each"),
    (dict(layer_types=("full_attention", "chunked_attention") + TINY.layer_types[2:]), "layer_types knows"),
    (dict(mlp_layer_types=("dense", "moe", "sparse", "sparse", "sparse")), "mlp_layer_types knows"),
    (dict(num_heads_per_layer=(4, 6, 6, 8, 4)), "one query-head count"),
    (dict(num_heads_per_layer=(4, 5, 5, 5, 4)), "multiple of num_kv_heads"),
    (dict(sliding_window=0), "sliding_window must be positive"),
])
def test_a_config_whose_lists_disagree_is_refused(wrong, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(TINY, **wrong)


def test_specs_follow_the_params_and_tp_divides_every_head_count():
    model = LagunaForCausalLM(BIG)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    specs = model.specs()
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    for tp in (2, 4, 8):
        assert BIG.heads_of(FULL) % tp == BIG.heads_of(WINDOW) % tp == BIG.num_kv_heads % tp == 0
    gate = specs["window_layers"]["attn"]["out_gate"]["kernel"]
    assert tuple(gate) == (None, None, "tp")
    # 33.4 B parameters as published
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 33.3e9 < total < 33.6e9


def test_the_registry_has_the_family_and_the_hf_names_round_trip(params):
    entry = model_registry()["tiny-laguna"]
    assert entry["model_cls"] is LagunaForCausalLM and entry["config"] is TINY
    sd = entry["to_hf"](params, TINY)
    assert sd["model.layers.1.self_attn.g_proj.weight"].shape == (6, 64)
    assert sd["model.layers.4.mlp.experts.7.down_proj.weight"].shape == (64, 32)
    assert sd["model.layers.0.mlp.up_proj.weight"].shape == (128, 64)
    assert "model.layers.0.mlp.gate.weight" not in sd and sd["model.layers.2.mlp.gate.weight"].shape == (8, 64)
    back = entry["from_hf"](sd, TINY)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree.leaves_with_path(params), jax.tree.leaves(back)):
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
    assert params_from_hf_laguna is entry["from_hf"] and params_to_hf_laguna is entry["to_hf"]
