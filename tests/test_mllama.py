"""Mllama (Llama-3.2 Vision) tests: logits parity vs HF transformers on a
tiny config (the 11B-Vision family named in BASELINE.json; the reference
repo ships no vision modeling code, so HF is the oracle)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.models.mllama import (
    MllamaConfig,
    MllamaForConditionalGeneration,
    MllamaTextConfig,
    MllamaVisionConfig,
    mllama_params_from_hf,
    prepare_cross_attention_mask,
)
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import shard_pytree

TINY = MllamaConfig(
    vision=MllamaVisionConfig(
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=3,
        num_global_layers=2,
        attention_heads=2,
        image_size=28,
        patch_size=14,
        max_num_tiles=2,
        max_aspect_ratio_id=3,
        intermediate_layers_indices=(0, 2),
    ),
    text=MllamaTextConfig(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=4,
        num_heads=4,
        num_kv_heads=2,
        cross_attention_layers=(1, 3),
        rope_theta=10000.0,
        max_seq_len=64,
    ),
)


def _hf_tiny():
    import torch
    from transformers import MllamaForConditionalGeneration as HF
    from transformers.models.mllama.configuration_mllama import (
        MllamaConfig as HFConfig,
        MllamaTextConfig as HFText,
        MllamaVisionConfig as HFVision,
    )

    c = TINY
    hf_cfg = HFConfig(
        vision_config=HFVision(
            hidden_size=c.vision.hidden_size,
            intermediate_size=c.vision.intermediate_size,
            num_hidden_layers=c.vision.num_hidden_layers,
            num_global_layers=c.vision.num_global_layers,
            attention_heads=c.vision.attention_heads,
            image_size=c.vision.image_size,
            patch_size=c.vision.patch_size,
            max_num_tiles=c.vision.max_num_tiles,
            intermediate_layers_indices=list(c.vision.intermediate_layers_indices),
            supported_aspect_ratios=[[1, 1], [1, 2], [2, 1]],
            vision_output_dim=c.vision.output_dim,
        ),
        text_config=HFText(
            vocab_size=c.text.vocab_size,
            hidden_size=c.text.hidden_size,
            intermediate_size=c.text.intermediate_size,
            num_hidden_layers=c.text.num_hidden_layers,
            num_attention_heads=c.text.num_heads,
            num_key_value_heads=c.text.num_kv_heads,
            cross_attention_layers=list(c.text.cross_attention_layers),
            rope_theta=c.text.rope_theta,
            rope_scaling={"rope_type": "default"},
            max_position_embeddings=c.text.max_seq_len,
            tie_word_embeddings=False,
            pad_token_id=0,
            bos_token_id=1,
            eos_token_id=2,
        ),
        image_token_index=3,
    )
    torch.manual_seed(0)
    model = HF(hf_cfg).eval()
    return model


def _inputs(seed=0, b=2, s=24):
    rng = np.random.default_rng(seed)
    c = TINY
    pix = rng.standard_normal(
        (b, 1, c.vision.max_num_tiles, 3, c.vision.image_size, c.vision.image_size)
    ).astype(np.float32)
    ids = rng.integers(0, c.text.vocab_size, (b, s)).astype(np.int64)
    ar_ids = np.array([[1], [2]])  # (1,1) and (1,2) aspect ratios
    ar_mask = np.array([[[1, 0]], [[1, 1]]])  # second image uses both tiles
    # text tokens attend image 0's valid tiles from position 4 on
    xmask = np.zeros((b, s, 1, c.vision.max_num_tiles), np.int64)
    xmask[0, 4:, 0, 0] = 1
    xmask[1, 4:, 0, :] = 1
    return pix, ids, ar_ids, ar_mask, xmask


@pytest.fixture(scope="module")
def hf_and_params():
    hf = _hf_tiny()
    params = mllama_params_from_hf(hf.state_dict(), TINY)
    return hf, params


def test_vision_encoder_matches_hf(hf_and_params):
    import torch

    hf, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    with torch.no_grad():
        ref = hf.model.vision_model(
            torch.tensor(pix), torch.tensor(ar_ids), torch.tensor(ar_mask)
        ).last_hidden_state.numpy()

    from neuronx_distributed_llama3_2_tpu.models.mllama import MllamaVisionModel

    out = jax.jit(MllamaVisionModel(TINY.vision).__call__)(
        params["vision_model"], jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask),
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4, rtol=1e-3)


def test_full_model_logits_match_hf(hf_and_params):
    import torch

    hf, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    with torch.no_grad():
        ref = hf(
            input_ids=torch.tensor(ids),
            pixel_values=torch.tensor(pix),
            aspect_ratio_ids=torch.tensor(ar_ids),
            aspect_ratio_mask=torch.tensor(ar_mask),
            cross_attention_mask=torch.tensor(xmask),
        ).logits.numpy()

    model = MllamaForConditionalGeneration(TINY)
    out = jax.jit(model.__call__)(
        params, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-3, rtol=1e-3)


def test_full_row_mask_zeroes_textonly_rows():
    xmask = np.zeros((1, 6, 1, 2), np.int64)
    xmask[0, 3:, 0, 0] = 1
    bias, full_row = prepare_cross_attention_mask(jnp.asarray(xmask), 5)
    assert full_row.shape == (1, 1, 6, 1)
    np.testing.assert_array_equal(
        np.asarray(full_row[0, 0, :, 0]), [0, 0, 0, 1, 1, 1]
    )
    # masked-out rows have all-NEG bias rows before scaling
    assert float(bias[0, 0, 0].max()) == 0.0  # zeroed by full_row multiply


def test_mllama_under_tp(hf_and_params):
    """tp=4 sharded execution matches the unsharded logits."""
    _, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    model = MllamaForConditionalGeneration(TINY)
    ref = jax.jit(model.__call__)(
        params, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    parallel_state.initialize_model_parallel(tensor_model_parallel_size=4)
    sharded = shard_pytree(params, model.specs())
    out = jax.jit(model.__call__)(
        sharded, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=1e-3
    )


def test_mllama_loss_and_grads_finite(hf_and_params):
    _, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    model = MllamaForConditionalGeneration(TINY)
    loss, grads = jax.jit(
        jax.value_and_grad(
            lambda p: model.loss(
                p, jnp.asarray(ids), jnp.asarray(ids), jnp.asarray(pix),
                jnp.asarray(ar_ids), jnp.asarray(ar_mask), jnp.asarray(xmask),
            )
        )
    )(params)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    # cross-attn gates are zero-init: they still receive gradient signal
    from neuronx_distributed_llama3_2_tpu.models.mllama import (
        text_group_pattern,
        text_layer_slice,
    )

    lp, is_cross = text_layer_slice(
        grads["layers"], 1, text_group_pattern(TINY.text)
    )
    assert is_cross
    assert float(jnp.abs(lp["cross_attn_attn_gate"]).max()) > 0


def test_vision_remat_full_matches_none(hf_and_params):
    """vision remat="full" (the 11B memory-plan requirement,
    docs/mllama_memory_plan.md) is numerically a no-op: identical loss and
    gradients, only the backward's recompute schedule changes."""
    import dataclasses

    _, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()

    def loss_and_grads(cfg):
        model = MllamaForConditionalGeneration(cfg)
        return jax.jit(
            jax.value_and_grad(
                lambda p: model.loss(
                    p, jnp.asarray(ids), jnp.asarray(ids), jnp.asarray(pix),
                    jnp.asarray(ar_ids), jnp.asarray(ar_mask),
                    jnp.asarray(xmask),
                )
            )
        )(params)

    base_loss, base_grads = loss_and_grads(TINY)
    remat_cfg = dataclasses.replace(
        TINY, vision=dataclasses.replace(TINY.vision, remat="full")
    )
    remat_loss, remat_grads = loss_and_grads(remat_cfg)
    np.testing.assert_allclose(float(base_loss), float(remat_loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(base_grads), jax.tree.leaves(remat_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_text_group_pattern_regular_and_irregular():
    """The grouped scan layout engages exactly when the cross-attn layers
    form the HF-regular xpos + g*k pattern (11B: stride 5, offset 3); an
    irregular config falls back to the per-layer list."""
    import dataclasses

    from neuronx_distributed_llama3_2_tpu.models.mllama import (
        MLLAMA_CONFIGS,
        text_group_pattern,
    )

    big = MLLAMA_CONFIGS["llama3.2-11b-vision"].text
    assert text_group_pattern(big) == (8, 5, 3)
    assert text_group_pattern(TINY.text) == (2, 2, 1)
    irregular = dataclasses.replace(big, cross_attention_layers=(3, 8, 14))
    assert text_group_pattern(irregular) is None
    # irregular configs still construct + run (the list/loop fallback)
    irr_tiny = dataclasses.replace(
        TINY, text=dataclasses.replace(
            TINY.text, cross_attention_layers=(1, 2)
        )
    )
    model = MllamaForConditionalGeneration(irr_tiny)
    params = model.init(jax.random.key(0))
    assert isinstance(params["layers"], list)
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    logits = jax.jit(
        lambda p: model(
            p, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
            jnp.asarray(ar_mask), jnp.asarray(xmask),
        )
    )(params)
    assert np.isfinite(np.asarray(logits)).all()


def test_mllama_under_tp_sequence_parallel(hf_and_params):
    """tp=4 + Megatron SP over the text stream matches the unsharded
    logits — the sharding layout the 11B memory plan depends on
    (docs/mllama_memory_plan.md: the Lt·S activation term divides by tp)."""
    _, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    model = MllamaForConditionalGeneration(TINY)
    ref = jax.jit(model.__call__)(
        params, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=4, sequence_parallel=True
    )
    sharded = shard_pytree(params, model.specs())
    out = jax.jit(model.__call__)(
        sharded, jnp.asarray(ids), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=1e-3
    )


def test_text_group_pattern_rejects_all_cross_layers():
    """k=1 (every layer cross-attn) would pack an EMPTY plain stack — the
    pattern must reject it so init falls back to the list layout instead
    of crashing in _stack_trees([])."""
    import dataclasses

    from neuronx_distributed_llama3_2_tpu.models.mllama import (
        text_group_pattern,
    )

    all_cross = dataclasses.replace(
        TINY.text, num_hidden_layers=2, cross_attention_layers=(0, 1)
    )
    assert text_group_pattern(all_cross) is None
    cfg = dataclasses.replace(TINY, text=all_cross)
    model = MllamaForConditionalGeneration(cfg)
    params = model.init(jax.random.key(0))
    assert isinstance(params["layers"], list) and len(params["layers"]) == 2


def test_mllama_tp_with_indivisible_vocab(hf_and_params):
    """When tp doesn't divide the vocab (tp=16 with the 128256+8-row
    embedding — the 11B fitting config's blocker), the embed falls back to
    embedding-dim sharding and the head to input-dim sharding; logits must
    match the unsharded model exactly. Simulated here with a vocab that
    tp=8 does not divide."""
    import dataclasses

    _, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()

    # TINY vocab 128: divisible by 8. Test the fallback decision logic on
    # a config whose vocab is NOT: trim both tables to vocab 124.
    cfg = dataclasses.replace(
        TINY, text=dataclasses.replace(TINY.text, vocab_size=124)
    )
    model = MllamaForConditionalGeneration(cfg)
    p124 = dict(params)
    p124["embed"] = {"embedding": params["embed"]["embedding"][: 124 + 8]}
    p124["lm_head"] = {"kernel": params["lm_head"]["kernel"][:, :124]}
    ids124 = np.minimum(ids, 123)

    ref = jax.jit(model.__call__)(
        p124, jnp.asarray(ids124), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=8, sequence_parallel=True
    )
    specs = model.specs()
    from jax.sharding import PartitionSpec as _P

    # embed rows 132 % 8 != 0 -> embed-dim sharding; vocab 124 % 8 != 0 ->
    # input-dim (Row-parallel) head
    assert specs["embed"]["embedding"] == _P(None, "tp")
    assert specs["lm_head"]["kernel"] == _P("tp", None)
    sharded = shard_pytree(p124, specs)
    out = jax.jit(model.__call__)(
        sharded, jnp.asarray(ids124), jnp.asarray(pix), jnp.asarray(ar_ids),
        jnp.asarray(ar_mask), jnp.asarray(xmask),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=1e-3
    )


def test_mllama_loss_with_indivisible_vocab(hf_and_params):
    """The CE path under the Row-parallel head fallback: logits arrive
    replicated over tp, so parallel_cross_entropy must take the plain-CE
    branch rather than the vocab-sharded shard_map (which cannot split an
    indivisible vocab). Loss must match the unsharded model."""
    import dataclasses

    _, params = hf_and_params
    pix, ids, ar_ids, ar_mask, xmask = _inputs()
    cfg = dataclasses.replace(
        TINY, text=dataclasses.replace(TINY.text, vocab_size=124)
    )
    model = MllamaForConditionalGeneration(cfg)
    p124 = dict(params)
    p124["embed"] = {"embedding": params["embed"]["embedding"][: 124 + 8]}
    p124["lm_head"] = {"kernel": params["lm_head"]["kernel"][:, :124]}
    ids124 = jnp.asarray(np.minimum(ids, 123))

    def loss_of(p):
        return model.loss(
            p, ids124, ids124, jnp.asarray(pix), jnp.asarray(ar_ids),
            jnp.asarray(ar_mask), jnp.asarray(xmask),
        )

    ref = float(jax.jit(loss_of)(p124))
    parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=8, sequence_parallel=True
    )
    sharded = shard_pytree(p124, model.specs())
    got = float(jax.jit(loss_of)(sharded))
    np.testing.assert_allclose(got, ref, rtol=1e-5)
