"""Mllama generation tests: greedy continuation parity vs HF
MllamaForConditionalGeneration.generate on the tiny config."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_llama3_2_tpu.inference.mllama_decode import MllamaDecoder

import sys
import os

sys.path.insert(0, os.path.dirname(__file__))
from test_mllama import TINY, _hf_tiny, _inputs  # noqa: E402


@pytest.fixture(scope="module")
def setup():
    from neuronx_distributed_llama3_2_tpu.models.mllama import (
        mllama_params_from_hf,
    )

    hf = _hf_tiny()
    params = mllama_params_from_hf(hf.state_dict(), TINY)
    return hf, params


def test_generate_matches_hf_greedy(setup):
    import torch

    hf, params = setup
    pix, ids, ar_ids, ar_mask, xmask = _inputs(b=2, s=12)
    # single-sequence decode: row 0 (attends image 0's first tile from pos 4)
    pix, ids, ar_ids, ar_mask, xmask = (
        pix[:1], ids[:1], ar_ids[:1], ar_mask[:1], xmask[:1]
    )

    with torch.no_grad():
        ref = hf.generate(
            input_ids=torch.tensor(ids),
            pixel_values=torch.tensor(pix),
            aspect_ratio_ids=torch.tensor(ar_ids),
            aspect_ratio_mask=torch.tensor(ar_mask),
            cross_attention_mask=torch.tensor(xmask),
            max_new_tokens=10,
            do_sample=False,
        )[0, ids.shape[1]:].tolist()

    dec = MllamaDecoder(TINY, params, max_seq_len=64)
    out = dec.generate(
        list(ids[0]),
        jnp.asarray(pix), jnp.asarray(ar_ids), jnp.asarray(ar_mask),
        jnp.asarray(xmask), max_new_tokens=10,
    )
    assert out == ref, (out, ref)


def test_prefill_logits_match_full_forward(setup):
    """Decode-path prefill logits == the training model's forward."""
    from neuronx_distributed_llama3_2_tpu.models.mllama import (
        MllamaForConditionalGeneration,
        prepare_cross_attention_mask,
    )

    _, params = setup
    pix, ids, ar_ids, ar_mask, xmask = _inputs(b=2, s=12)
    pix, ids, ar_ids, ar_mask, xmask = (
        pix[:1], ids[:1], ar_ids[:1], ar_mask[:1], xmask[:1]
    )
    model = MllamaForConditionalGeneration(TINY)
    ref = jax.jit(model.__call__)(
        params, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )

    dec = MllamaDecoder(TINY, params, max_seq_len=32)
    from neuronx_distributed_llama3_2_tpu.inference.mllama_decode import (
        MllamaCache,
    )

    _, ck, cv = dec.precompute_cross_kv(
        jnp.asarray(pix), jnp.asarray(ar_ids), jnp.asarray(ar_mask)
    )
    t = TINY.text
    cache = MllamaCache(
        k=[jnp.zeros((1, 32, t.num_kv_heads, t.head_dim), t.dtype)
           for _ in dec._self_layers],
        v=[jnp.zeros((1, 32, t.num_kv_heads, t.head_dim), t.dtype)
           for _ in dec._self_layers],
        cross_k=ck, cross_v=cv,
    )
    bias, full = prepare_cross_attention_mask(
        jnp.asarray(xmask), TINY.vision.num_patches
    )
    logits, _ = jax.jit(dec.forward)(
        params, cache, jnp.asarray(ids, jnp.int32),
        jnp.zeros((1,), jnp.int32), bias, full,
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref), atol=2e-4, rtol=1e-3
    )


def test_generate_eos_and_zero_budget(setup):
    _, params = setup
    pix, ids, ar_ids, ar_mask, xmask = _inputs(b=2, s=12)
    pix, ids, ar_ids, ar_mask, xmask = (
        pix[:1], ids[:1], ar_ids[:1], ar_mask[:1], xmask[:1]
    )
    dec = MllamaDecoder(TINY, params, max_seq_len=64)
    args = (list(ids[0]), jnp.asarray(pix), jnp.asarray(ar_ids),
            jnp.asarray(ar_mask), jnp.asarray(xmask))
    assert dec.generate(*args, max_new_tokens=0) == []
    full = dec.generate(*args, max_new_tokens=6)
    # treating the first emitted token as EOS stops after exactly one token
    assert dec.generate(*args, max_new_tokens=6, eos_token_id=full[0]) == full[:1]


def test_quantized_tree_serves_in_jit(setup):
    """int8 trees serve through MllamaDecoder with in-jit dequant (was a
    NotImplementedError refusal): generation equals serving the
    host-dequantized tree — identical computation, exact match."""
    from neuronx_distributed_llama3_2_tpu.quantization import (
        QuantizedTensor,
        dequantize_params,
        quantize_params,
    )

    _, params = setup
    pix, ids, ar_ids, ar_mask, xmask = _inputs(b=2, s=12)
    pix, ids, ar_ids, ar_mask, xmask = (
        pix[:1], ids[:1], ar_ids[:1], ar_mask[:1], xmask[:1]
    )
    args = (
        list(ids[0]), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )

    qparams = quantize_params(params)
    n_q = sum(
        isinstance(l, QuantizedTensor)
        for l in jax.tree.leaves(
            qparams, is_leaf=lambda l: isinstance(l, QuantizedTensor)
        )
    )
    assert n_q > 0, "quantize_params matched no mllama kernels"
    # coverage: text self+cross attention, vision attention/MLP and the
    # projector all quantize (review finding: only o-projections matched
    # before the Mllama patterns were added to DEFAULT_TARGETS)
    from neuronx_distributed_llama3_2_tpu.quantization.quantize import walk_tree

    q_paths = []
    walk_tree(qparams, lambda p, l: q_paths.append(p)
          if isinstance(l, QuantizedTensor) else l)
    assert any("cross_attn/q/kernel" in p for p in q_paths), q_paths[:10]
    assert any("vision_model" in p and "self_attn/q/kernel" in p for p in q_paths)
    assert any("mlp/fc1/kernel" in p for p in q_paths)
    assert any("multi_modal_projector" in p for p in q_paths)

    out_q = MllamaDecoder(TINY, qparams, max_seq_len=64).generate(
        *args, max_new_tokens=8
    )
    deq = dequantize_params(qparams, TINY.text.dtype)
    out_ref = MllamaDecoder(TINY, deq, max_seq_len=64).generate(
        *args, max_new_tokens=8
    )
    assert out_q == out_ref, (out_q, out_ref)
