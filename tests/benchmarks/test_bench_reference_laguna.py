"""The plain Laguna reference against the program's own training model at the
tiny size: same weights, same tokens, float32 on the CPU — the ways of getting
this model wrong that the same tolerance has to tell apart, and what the
configuration file and the family module hold."""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec

RTOL = ATOL = 1e-4
CONFIG = os.path.join(spec.HERE, "configs", "laguna-xs2-1chip.json")


@pytest.fixture(scope="module")
def case():
    fam = spec.load_family("laguna")
    with open(CONFIG) as f:
        cfg = json.load(f)
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert model_cfg.kinds == ("full", "window", "window", "window", "full")
    assert model_cfg.num_heads_per_layer == (4, 6, 6, 6, 4) and model_cfg.sliding_window == 8
    def perturbed(key):
        """Every leaf moved off its initial value (norm scales start at one)."""
        leaves, tree = jax.tree.flatten(fam.train_model(model_cfg).init(key))
        keys = jax.random.split(jax.random.key(4), len(leaves))
        return jax.tree.unflatten(tree, [
            p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)])

    params = jax.jit(perturbed)(jax.random.key(3))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 48)), jnp.int32)
    ref_cfg = fam.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids))
    # the reference's loss is its logits' (one compile of the forward, not two)
    from benchmarks.reference.common import next_token_loss
    want_loss = float(next_token_loss(jnp.asarray(want), ids))
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


def test_the_margin_is_the_gap_at_the_last_expert_taken(case):
    fam, model_cfg, params, ids, want, _ = case
    ref_cfg = fam.reference_config(model_cfg)
    logits, margin = jax.jit(lambda p, i: fam.reference.forward_with_margin(p, ref_cfg, i))(params, ids)
    assert margin.shape == ids.shape and float(margin.min()) >= 0.0 and float(margin.max()) <= 1.0
    np.testing.assert_allclose(np.asarray(logits), want, rtol=1e-6, atol=1e-6)


def test_the_reference_is_causal_and_takes_nothing_from_the_program(case):
    fam, model_cfg, params, ids, want, _ = case
    ref_cfg = fam.reference_config(model_cfg)
    with jax.default_matmul_precision("highest"):
        short = np.asarray(jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids[:, :20]))
    np.testing.assert_allclose(short, want[:, :20], rtol=1e-5, atol=1e-5)
    with open(os.path.join(spec.HERE, "reference", "laguna.py")) as f:
        source = f.read()
    assert "neuronx_distributed" not in source and "top_k_routing" not in source


def test_the_published_file_keeps_every_width_and_states_the_cut(case):
    fam = case[0]
    with open(CONFIG) as f:
        cfg = json.load(f)
    big = fam.model_config(cfg, rehearsal=False, max_seq_len=8704)
    assert (big.hidden_size, big.head_dim, big.num_kv_heads, big.intermediate_size) == (2048, 128, 8, 8192)
    assert (big.num_experts, big.top_k, big.moe_intermediate_size, big.shared_expert_intermediate_size) == (256, 8, 512, 512)
    assert (big.num_layers, big.vocab_size, big.sliding_window, big.routed_scaling_factor) == (5, 100352, 512, 2.5)
    assert big.num_heads_per_layer == (48, 64, 64, 64, 48) and big.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert big.kinds == ("full", "window", "window", "window", "full")
    assert (big.rope_theta, big.window_rope_theta, big.partial_rotary_factor) == (500000.0, 10000.0, 0.5)
    assert big.yarn == (64.0, 4096, 64.0, 1.0, 1.4158883083359672)
    assert set(cfg["reduced"]) == {"num_hidden_layers"} and cfg["reduced"]["num_hidden_layers"]["published"] == 40
    assert {"gating", "router", "shared_expert", "sliding_window", "rotary_pairing", "yarn", "qk_norm"} <= set(cfg["assumed"])
    assert "AGAINST the lineage" in cfg["assumed"]["shared_expert"]
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        row = next(c for c in json.load(f)["configs"] if c["name"] == "laguna-xs2-1chip")
    assert row["reduced"] == ["num_hidden_layers"] and row["source"] == cfg["source"]


@pytest.mark.parametrize("spoil, match", [
    (lambda c: c.update(layer_types=c["layer_types"][:4]), "entries each"),
    (lambda c: c.update(mlp_layer_types=c["mlp_layer_types"] + ["sparse"]), "entries each"),
    (lambda c: c["layer_types"].__setitem__(1, "chunked_attention"), "layer_types knows"),
    (lambda c: c["mlp_layer_types"].__setitem__(2, "moe"), "mlp_layer_types knows"),
])
def test_the_family_refuses_a_file_whose_lists_disagree(case, spoil, match):
    with open(CONFIG) as f:
        cfg = copy.deepcopy(json.load(f))
    spoil(cfg)
    with pytest.raises(ValueError, match=match):
        case[0].model_config(cfg, rehearsal=False)


def gate_skipped(monkeypatch):
    from neuronx_distributed_llama3_2_tpu.models import laguna

    def output(self, params, h, att):
        b, t = att.shape[:2]
        return self._llama()._o()(params["o"], att.reshape(b, t, -1))

    monkeypatch.setattr(laguna.LagunaAttention, "output", output)


def gate_after_the_output_projection(monkeypatch):
    from neuronx_distributed_llama3_2_tpu.models import laguna

    def output(self, params, h, att):
        b, t = att.shape[:2]
        gate = jax.nn.sigmoid(h @ params["out_gate"]["kernel"]).mean(-1, keepdims=True)
        return gate * self._llama()._o()(params["o"], att.reshape(b, t, -1))

    monkeypatch.setattr(laguna.LagunaAttention, "output", output)


WRONG = {
    "window_lower_bound_dropped": lambda c: dataclasses.replace(c, sliding_window=10 ** 6),
    "window_one_key_short": lambda c: dataclasses.replace(c, sliding_window=c.sliding_window - 1),
    "scale_dropped": lambda c: dataclasses.replace(c, routed_scaling_factor=1.0),
    "gates_not_renormalised": lambda c: dataclasses.replace(c, normalize_top_k=False),
    "last_expert_dropped": lambda c: dataclasses.replace(c, top_k=c.top_k - 1),
    "one_rotary_table_for_both_kinds": lambda c: dataclasses.replace(c, window_rope_theta=c.rope_theta),
    "no_yarn": lambda c: dataclasses.replace(c, yarn=None),
    "whole_head_rotated_in_full_layers": lambda c: dataclasses.replace(c, partial_rotary_factor=1.0),
    "gate_skipped": lambda c: c,
    "gate_after_the_output_projection": lambda c: c,
}


@pytest.mark.parametrize("mistake", sorted(WRONG))
def test_a_wrong_laguna_exceeds_the_tolerance(case, mistake, monkeypatch):
    fam, model_cfg, params, ids, want, _ = case
    if mistake.startswith("gate_"):
        globals()[mistake](monkeypatch)
    got, _ = program(fam, WRONG[mistake](model_cfg), params, ids)
    # rows inside the first window see the same keys whatever the window's bound
    rows = slice(model_cfg.sliding_window, None) if mistake.startswith("window_") else slice(None)
    excess = np.abs(got - want)[:, rows] - (ATOL + RTOL * np.abs(want[:, rows]))
    assert (excess > 0).mean() > 0.5, f"{mistake}: only {(excess > 0).mean():.1%} of logits differ"
    assert np.abs(got - want).max() > 50 * ATOL
