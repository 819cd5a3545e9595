"""Quantization tests (VERDICT #8: roundtrip + quantized tiny-llama forward
tracking fp logits; reference test strategy test_quantization_layers.py /
test_quantize.py under SURVEY §2.6)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    shard_pytree,
)
from neuronx_distributed_llama3_2_tpu.quantization import (
    QuantizationConfig,
    QuantizationType,
    QuantizedColumnParallelLinear,
    QuantizedRowParallelLinear,
    QuantizedTensor,
    convert,
    dequantize_params,
    quantize_array,
    quantize_params,
    quantize_specs,
)

TINY = LLAMA_CONFIGS["tiny"]


# ---------------------------------------------------------------------------
# quantize/dequantize roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "qtype",
    [QuantizationType.PER_TENSOR_SYMMETRIC, QuantizationType.PER_CHANNEL_SYMMETRIC],
)
def test_int8_roundtrip_error_bounded(qtype):
    """|dequant(quant(w)) - w| <= scale/2 elementwise (symmetric rounding)."""
    w = jax.random.normal(jax.random.key(0), (64, 48), jnp.float32) * 0.1
    cfg = QuantizationConfig(quantization_type=qtype)
    qt = quantize_array(w, cfg)
    assert qt.qvalue.dtype == jnp.int8
    err = np.abs(np.asarray(qt.dequantize(jnp.float32)) - np.asarray(w))
    half_step = np.asarray(qt.scale) / 2 + 1e-8
    assert (err <= np.broadcast_to(half_step, err.shape)).all()


def test_per_channel_beats_per_tensor_on_skewed_weights():
    """Per-channel scales exist because rows/cols differ in magnitude; check
    the error ordering that motivates the reference default."""
    key = jax.random.key(1)
    w = jax.random.normal(key, (32, 32), jnp.float32)
    w = w * jnp.logspace(-2, 0, 32)[None, :]  # skew output channels
    pc = quantize_array(
        w, QuantizationConfig(QuantizationType.PER_CHANNEL_SYMMETRIC)
    )
    pt = quantize_array(
        w, QuantizationConfig(QuantizationType.PER_TENSOR_SYMMETRIC)
    )
    err_pc = float(jnp.abs(pc.dequantize(jnp.float32) - w).mean())
    err_pt = float(jnp.abs(pt.dequantize(jnp.float32) - w).mean())
    assert err_pc < err_pt


def test_fp8_roundtrip():
    w = jax.random.normal(jax.random.key(2), (16, 16), jnp.float32) * 0.05
    qt = quantize_array(w, QuantizationConfig(quantized_dtype="fp8_e4m3"))
    assert qt.qvalue.dtype == jnp.float8_e4m3fn
    np.testing.assert_allclose(
        np.asarray(qt.dequantize(jnp.float32)), np.asarray(w), atol=0.01
    )


def test_stacked_kernels_get_per_layer_scales():
    """(L, in, out) stacks must not share scales across L: a layer with
    100x-smaller weights keeps its own precision (review finding)."""
    w = jnp.stack([
        jax.random.normal(jax.random.key(0), (8, 16)) * 0.01,
        jax.random.normal(jax.random.key(1), (8, 16)),
        jax.random.normal(jax.random.key(2), (8, 16)),
    ])
    qt = quantize_array(w)
    assert qt.scale.shape == (3, 1, 16)
    err0 = float(jnp.abs(qt.dequantize(jnp.float32)[0] - w[0]).mean())
    rel0 = err0 / float(jnp.abs(w[0]).mean())
    assert rel0 < 0.01, rel0


def test_quantized_tensor_is_pytree_node():
    qt = quantize_array(jnp.ones((4, 4)))
    leaves = jax.tree.leaves(qt)
    assert len(leaves) == 2  # qvalue + scale


# ---------------------------------------------------------------------------
# quantized layers (reference quantization_layers.py:342,507)
# ---------------------------------------------------------------------------

def test_quantized_column_parallel_matches_float():
    layer = ColumnParallelLinear(32, 64, use_bias=True, dtype=jnp.float32)
    params = layer.init(jax.random.key(3))
    qlayer = convert(layer)
    qparams = qlayer.quantize_params(params)
    x = jax.random.normal(jax.random.key(4), (2, 8, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(qlayer(qparams, x)),
        np.asarray(layer(params, x)),
        atol=0.05,
        rtol=0.05,
    )


def test_quantized_row_parallel_matches_float_under_tp():
    parallel_state.initialize_model_parallel(tensor_model_parallel_size=4)
    layer = RowParallelLinear(64, 32, dtype=jnp.float32)
    params = layer.init(jax.random.key(5))
    qlayer = QuantizedRowParallelLinear.from_float(layer)
    qparams = qlayer.quantize_params(params)
    # shard payload + scale per specs; dequant must commute with the
    # partial-sum all-reduce
    qparams_sharded = shard_pytree(
        {"kernel": qparams["kernel"].qvalue}, {"kernel": P("tp", None)}
    )
    qparams = {
        "kernel": QuantizedTensor(qparams_sharded["kernel"], qparams["kernel"].scale)
    }
    x = jax.random.normal(jax.random.key(6), (2, 8, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(jax.jit(qlayer.__call__)(qparams, x)),
        np.asarray(layer(params, x)),
        atol=0.05,
        rtol=0.05,
    )


def test_convert_rejects_unmapped():
    with pytest.raises(TypeError):
        convert(object())


# ---------------------------------------------------------------------------
# whole-model quantization (reference quantize.convert over a model)
# ---------------------------------------------------------------------------

def _n_quantized(tree):
    return sum(
        isinstance(l, QuantizedTensor)
        for l in jax.tree.leaves(
            tree, is_leaf=lambda l: isinstance(l, QuantizedTensor)
        )
    )


def test_quantize_params_targets_projections_only():
    model = LlamaForCausalLM(TINY)
    params = model.init(jax.random.key(7))
    qparams = quantize_params(params)
    # qkv(3) + o + gate_up + down per stacked-layer tree = 6 quantized leaves
    assert _n_quantized(qparams) == 6
    # embedding + norms untouched
    assert isinstance(qparams["embed"]["embedding"], jax.Array)


def test_quantized_tiny_llama_logits_track_fp():
    """VERDICT #8 'done' condition."""
    model = LlamaForCausalLM(TINY)
    params = model.init(jax.random.key(8))
    qparams = quantize_params(params)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab_size, (2, 16)), jnp.int32
    )
    ref = jax.jit(model.__call__)(params, ids)
    out = jax.jit(lambda qp, i: model(dequantize_params(qp, TINY.dtype), i))(
        qparams, ids
    )
    # int8 weight-only: logits track fp within a loose tolerance
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32))
    assert err.max() < 0.25, err.max()
    # top-1 predictions nearly all agree
    agree = (np.asarray(out).argmax(-1) == np.asarray(ref).argmax(-1)).mean()
    assert agree > 0.95


def test_quantize_specs_matches_params_structure():
    parallel_state.initialize_model_parallel(tensor_model_parallel_size=2)
    model = LlamaForCausalLM(TINY)
    params = model.init(jax.random.key(9))
    specs = model.specs()
    qparams = quantize_params(params)
    qspecs = quantize_specs(params, specs)
    assert jax.tree.structure(
        qparams, is_leaf=lambda l: isinstance(l, QuantizedTensor)
    ) == jax.tree.structure(qspecs, is_leaf=lambda l: isinstance(l, QuantizedTensor))
    # sharded placement of a quantized tree works end to end
    placed = shard_pytree(qparams, qspecs)
    assert _n_quantized(placed) == 6


# ---------------------------------------------------------------------------
# MoE expert-fused quantization (reference QuantizedExpertFusedColumnParallel/
# RowParallel, quantization_layers.py:668,777)
# ---------------------------------------------------------------------------

def test_moe_expert_weights_quantized_with_per_expert_scales():
    from neuronx_distributed_llama3_2_tpu.models import (
        MIXTRAL_CONFIGS,
        MixtralForCausalLM,
    )

    cfg = MIXTRAL_CONFIGS["tiny-moe"]
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.key(10))
    qparams = quantize_params(params)
    # qkv(3) + o + expert gate_up + expert down = 6 quantized leaves
    assert _n_quantized(qparams) == 6
    gu = qparams["layers"]["moe"]["experts"]["gate_up"]  # (L, E, H, 2, I)
    dn = qparams["layers"]["moe"]["experts"]["down"]     # (L, E, I, H)
    L, E = cfg.num_layers, cfg.num_experts
    assert isinstance(gu, QuantizedTensor)
    # scales per (layer, expert, fused-proj, out-channel); contraction H shared
    assert gu.scale.shape == (L, E, 1, 2, cfg.intermediate_size)
    assert dn.scale.shape == (L, E, 1, cfg.hidden_size)
    # router stays float
    assert isinstance(qparams["layers"]["moe"]["router"]["kernel"], jax.Array)


def test_quantized_mixtral_logits_track_fp():
    from neuronx_distributed_llama3_2_tpu.models import (
        MIXTRAL_CONFIGS,
        MixtralForCausalLM,
    )

    cfg = MIXTRAL_CONFIGS["tiny-moe"]
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.key(11))
    qparams = quantize_params(params)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )
    ref = np.asarray(model(params, ids), np.float32)
    out = np.asarray(model(dequantize_params(qparams, cfg.dtype), ids), np.float32)
    assert np.abs(out - ref).max() < 0.25
    agree = (out.argmax(-1) == ref.argmax(-1)).mean()
    assert agree > 0.95


def test_quantized_moe_decode_generates():
    """int8 weights drive the MoE no-drop decode dispatch end to end."""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
        SamplingConfig,
    )
    from neuronx_distributed_llama3_2_tpu.models import (
        MIXTRAL_CONFIGS,
        MixtralForCausalLM,
    )

    cfg = MIXTRAL_CONFIGS["tiny-moe"]
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.key(12))
    fparams = dequantize_params(quantize_params(params), cfg.dtype)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (6,)).tolist()
    engine = InferenceEngine(cfg, fparams, max_batch=1, max_seq_len=128)
    out = engine.generate(
        [prompt],
        GenerationConfig(max_new_tokens=4, sampling=SamplingConfig(greedy=True)),
    )
    seq, want = list(prompt), []
    for _ in range(4):
        logits = model(fparams, jnp.asarray([seq], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits[0, -1], np.float32)))
        want.append(nxt)
        seq.append(nxt)
    assert out.sequences[0] == want


def test_bert_projections_quantized():
    """BERT's attn/mlp nesting matches the family-wide target patterns
    (review finding: the flat layout silently escaped quantization)."""
    from neuronx_distributed_llama3_2_tpu.models import (
        BERT_CONFIGS,
        BertForPreTraining,
    )

    model = BertForPreTraining(BERT_CONFIGS["tiny-bert"])
    params = model.init(jax.random.key(13))
    qparams = quantize_params(params)
    # qkv(3) + o + up + down
    assert _n_quantized(qparams) == 6


def test_engine_serves_quantized_tree_directly():
    """The engine accepts a params tree with QuantizedTensor leaves and
    dequantizes inside each compiled program (int8 stays HBM-resident —
    reference run_llama_quantized.py serving mode); tokens match serving
    the pre-dequantized tree."""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
        SamplingConfig,
    )

    model = LlamaForCausalLM(TINY)
    params = model.init(jax.random.key(14))
    qparams = quantize_params(params)
    prompt = np.random.default_rng(3).integers(0, TINY.vocab_size, (8,)).tolist()
    g = GenerationConfig(max_new_tokens=6, sampling=SamplingConfig(greedy=True))

    eng_q = InferenceEngine(TINY, qparams, max_batch=1, max_seq_len=64)
    got = eng_q.generate([prompt], g).sequences[0]
    eng_f = InferenceEngine(
        TINY, dequantize_params(qparams, TINY.dtype), max_batch=1, max_seq_len=64
    )
    want = eng_f.generate([prompt], g).sequences[0]
    assert got == want
