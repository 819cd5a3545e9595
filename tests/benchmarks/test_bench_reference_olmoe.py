"""The plain OLMoE reference against the program's own training model at the
tiny size: same weights, same tokens, float32 on the CPU — and four ways of
getting OLMoE wrong that the same tolerance has to tell apart."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec

# as the Mixtral and GPT-NeoX twins (test_bench_reference.py): float32 on both
# sides, two layers; a dropped term shows at 1e-2 and above
RTOL = ATOL = 2e-4


@pytest.fixture(scope="module")
def case():
    fam = spec.load_family("olmoe")
    with open(os.path.join(spec.HERE, "configs", "olmoe-1b-7b-1chip.json")) as f:
        cfg = json.load(f)
    model_cfg = fam.model_config(cfg, rehearsal=True)
    assert model_cfg.qk_norm and not model_cfg.normalize_top_k
    params = jax.jit(fam.train_model(model_cfg).init)(jax.random.key(3))
    # norm scales start at one: perturb every leaf so that a reference that
    # dropped one (the q/k norms' among them) would show
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype) for p, k in zip(leaves, keys)
    ])
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 48)), jnp.int32
    )
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
    # the program's training loss adds the router's load-balancing term; the
    # reference is the language-model loss alone
    assert want_loss <= got_loss + 1e-4
    assert abs(got_loss - want_loss) < 0.05 * abs(want_loss)


def test_the_margin_is_the_gap_between_the_last_expert_taken_and_the_first_left_out(case):
    fam, model_cfg, params, ids, want, _ = case
    ref_cfg = fam.reference_config(model_cfg)
    logits, margin = jax.jit(lambda p, i: fam.reference.forward_with_margin(p, ref_cfg, i))(params, ids)
    assert margin.shape == ids.shape and float(margin.min()) >= 0.0 and float(margin.max()) <= 1.0
    np.testing.assert_allclose(np.asarray(logits), want, rtol=1e-6, atol=1e-6)


def per_head_qk_norm(monkeypatch):
    """The program made to take the QK-norm head by head (the mistake the
    published wording invites): mean over ``head_dim`` alone."""
    from neuronx_distributed_llama3_2_tpu.models.llama import LlamaAttention

    def per_head(self, params, q, k):
        def norm(x, scale):
            var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(var + self.config.rms_norm_eps) * scale.reshape(x.shape[-2:])
        return norm(q, params["q_norm"]["scale"]), norm(k, params["k_norm"]["scale"])

    monkeypatch.setattr(LlamaAttention, "_qk_norm", per_head)


WRONG = {
    "qk_norm_skipped": lambda c: dataclasses.replace(c, qk_norm=False),
    "gates_renormalised": lambda c: dataclasses.replace(c, normalize_top_k=True),
    "last_expert_dropped": lambda c: dataclasses.replace(c, top_k=c.top_k - 1),
    "norm_per_head": lambda c: c,
}


@pytest.mark.parametrize("mistake", sorted(WRONG))
def test_a_wrong_olmoe_exceeds_the_tolerance(case, mistake, monkeypatch):
    """Negative controls: each is a program that is not OLMoE, on the same
    weights; the reference must differ from it by far more than the tolerance
    the right program is held to."""
    fam, model_cfg, params, ids, want, _ = case
    if mistake == "norm_per_head":
        per_head_qk_norm(monkeypatch)
    got, _ = program(fam, WRONG[mistake](model_cfg), params, ids)
    excess = np.abs(got - want) - (ATOL + RTOL * np.abs(want))
    assert (excess > 0).mean() > 0.5, f"{mistake}: only {(excess > 0).mean():.1%} of logits differ"
    assert np.abs(got - want).max() > 50 * ATOL
