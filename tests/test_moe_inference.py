"""MoE inference tests: the no-drop expert dispatch + Mixtral KV-cache decode.

Mirrors the reference's Mixtral inference model
(examples/inference/mixtral/neuron_modeling_mixtral.py) and its token-gen
dispatch (modules/moe/expert_mlps.py:267,298-357; the selective
expert-loading path is kept as a reference, ``__call__`` streams all experts):
decode must route/compute identically to the training model so incremental
generation equals full recompute.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference import (
    InferenceEngine,
    GenerationConfig,
    LlamaDecode,
    MixtralDecode,
    SamplingConfig,
    decode_model_for,
)
from neuronx_distributed_llama3_2_tpu.models import (
    LLAMA_CONFIGS,
    MIXTRAL_CONFIGS,
    OLMOE_CONFIGS,
    LlamaForCausalLM,
    MixtralForCausalLM,
    OlmoeForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.moe.experts import ExpertMLPs
from neuronx_distributed_llama3_2_tpu.moe.routing import top_k_routing

from tests.test_moe import _dense_reference

TINY_MOE = MIXTRAL_CONFIGS["tiny-moe"]
# the families MixtralDecode serves: (config, training model); OLMoE adds the
# joint QK-norm and gates that are not renormalised
FAMILIES = {
    "mixtral": (TINY_MOE, MixtralForCausalLM),
    "olmoe": (OLMOE_CONFIGS["tiny-olmoe"], OlmoeForCausalLM),
}


def _params():
    return MixtralForCausalLM(TINY_MOE).init(jax.random.key(0))


def test_selective_matches_all_experts():
    ex = ExpertMLPs(
        num_experts=8, hidden_size=16, intermediate_size=32, dtype=jnp.float32
    )
    params = ex.init(jax.random.key(1))
    t, k = 3, 2
    x = jax.random.normal(jax.random.key(2), (t, 16), jnp.float32)
    logits = jax.random.normal(jax.random.key(3), (t, 8), jnp.float32)
    gates, idx = top_k_routing(logits, k, normalize=True)
    y_sel = ex.forward_selective(params, x, gates, idx)
    y_all = ex.forward_all_experts(params, x, gates, idx)
    np.testing.assert_allclose(
        np.asarray(y_sel), np.asarray(y_all), atol=1e-5, rtol=1e-5
    )


def test_selective_dispatch_threshold(monkeypatch):
    """The no-drop branch of __call__ streams all experts at every shape —
    below T·k = E, at it and above it (the table above the rule in
    moe/experts.py says why). forward_selective stays as the reference."""
    ex = ExpertMLPs(
        num_experts=4, hidden_size=8, intermediate_size=16, dtype=jnp.float32
    )
    params = ex.init(jax.random.key(0))
    calls = []
    real_selective = ExpertMLPs.forward_selective
    monkeypatch.setattr(
        ExpertMLPs,
        "forward_selective",
        lambda self, *a, **k: (calls.append("sel"), real_selective(self, *a, **k))[1],
    )
    for t in (1, 2, 5):  # T·k = 2 < E, 4 = E, 10 > E
        x = jax.random.normal(jax.random.key(t), (t, 8), jnp.float32)
        logits = jax.random.normal(jax.random.key(t + 10), (t, 4), jnp.float32)
        gates, idx = top_k_routing(logits, 2, normalize=True)
        calls.clear()
        y = ex(params, x, gates, idx)
        assert not calls, t
        y_ref = real_selective(ex, params, x, gates, idx)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ref), atol=1e-5, rtol=1e-5
        )


@pytest.mark.parametrize("side", ["below", "at", "above"])
@pytest.mark.parametrize("experts,k", [(8, 2), (64, 8)])
def test_no_drop_dispatch_equals_selective_and_dense(experts, k, side):
    """__call__ on each side of T·k = E, at Mixtral's and OLMoE's expert
    counts, against forward_selective and the dense reference; gates are not
    renormalised for the second, as OLMoE's are not."""
    t = experts // k + {"below": -1, "at": 0, "above": 3}[side]
    ex = ExpertMLPs(
        num_experts=experts, hidden_size=16, intermediate_size=24, dtype=jnp.float32
    )
    params = ex.init(jax.random.key(experts))
    x = jax.random.normal(jax.random.key(t), (t, 16), jnp.float32)
    logits = jax.random.normal(jax.random.key(t + 1), (t, experts), jnp.float32)
    gates, idx = top_k_routing(logits, k, normalize=experts == 8)
    y = np.asarray(ex(params, x, gates, idx))
    np.testing.assert_allclose(
        y, np.asarray(ex.forward_selective(params, x, gates, idx)), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(y, _dense_reference(params, x, gates, idx), atol=1e-5, rtol=1e-5)


def test_decode_model_dispatch():
    assert isinstance(decode_model_for(TINY_MOE), MixtralDecode)
    llama = decode_model_for(LLAMA_CONFIGS["tiny"])
    assert isinstance(llama, LlamaDecode)
    assert not isinstance(llama, MixtralDecode)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_mixtral_incremental_decode_matches_recompute(family):
    """Prefill + per-token decode logits == full-model forward on the
    growing prefix (the MoE analogue of the Llama decode-parity gate)."""
    cfg, model_cls = FAMILIES[family]
    model = model_cls(cfg)
    params = model.init(jax.random.key(0))
    decode = decode_model_for(cfg)
    assert type(decode) is MixtralDecode
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    n_extra = 4

    cache = decode.init_cache(max_batch=1, max_len=32)
    ids = jnp.asarray(prompt)
    logits_pre, cache = decode.forward(
        params, cache, ids, jnp.zeros((1,), jnp.int32), context_encode=True
    )
    full = model(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_pre, np.float32), np.asarray(full, np.float32),
        atol=2e-4, rtol=2e-4,
    )

    seq = prompt[0].tolist()
    for step in range(n_extra):
        nxt = int(np.argmax(np.asarray(full)[0, -1]))
        seq.append(nxt)
        pos = jnp.asarray([len(seq) - 1], jnp.int32)
        logits_step, cache = decode.forward(
            params, cache, jnp.asarray([[nxt]], jnp.int32), pos
        )
        full = model(params, jnp.asarray([seq], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits_step[:, 0], np.float32),
            np.asarray(full)[:, -1].astype(np.float32),
            atol=3e-4, rtol=3e-4,
        )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_mixtral_engine_greedy_generate(family):
    """End-to-end: the bucketed engine generates the same greedy tokens as
    an argmax loop over the training model's full forward."""
    cfg, model_cls = FAMILIES[family]
    cfg = dataclasses.replace(cfg, max_seq_len=128)
    model = model_cls(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, size=(6,)).tolist()
    n_new = 5

    engine = InferenceEngine(cfg, params, max_batch=1, max_seq_len=128)
    out = engine.generate(
        [prompt],
        GenerationConfig(
            max_new_tokens=n_new, sampling=SamplingConfig(greedy=True)
        ),
    )
    got = out.sequences[0]

    seq = list(prompt)
    want = []
    for _ in range(n_new):
        logits = model(params, jnp.asarray([seq], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        want.append(nxt)
        seq.append(nxt)
    assert got == want


def test_mixtral_capacity_config_decode_never_drops():
    """A capacity-factor training config still decodes through the no-drop
    all-experts path (capacity dispatch is training-only)."""
    cfg = dataclasses.replace(TINY_MOE, capacity_factor=1.0)
    params = MixtralForCausalLM(cfg).init(jax.random.key(0))
    decode = MixtralDecode(cfg)
    cache = decode.init_cache(max_batch=2, max_len=16)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)), jnp.int32
    )
    logits, cache = decode.forward(
        params, cache, ids, jnp.zeros((2,), jnp.int32), context_encode=True
    )
    # no-capacity config must agree: same weights, same routing, no dropping
    ref_logits, _ = MixtralDecode(TINY_MOE).forward(
        params, decode.init_cache(2, 16), ids, jnp.zeros((2,), jnp.int32),
        context_encode=True,
    )
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(ref_logits, np.float32),
        atol=1e-5, rtol=1e-5,
    )
