"""The Jamba family's training-side model at the tiny size, float32 on the CPU:
the layer order from period and offset, the Mamba mixer's forms against one
another (a block of rows from a carried state and tail equals the whole
sequence; one token a lane equals a block of one), attention with no rotary
table, the published initialisation of what sets a state's memory, and the HF
names both ways. The plain reference is held in ``tests/benchmarks``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.models import resolve_model
from neuronx_distributed_llama3_2_tpu.models.jamba import (
    ATTENTION, JAMBA_CONFIGS, MAMBA, JambaForCausalLM, MambaMixer, layer_runs,
    params_from_hf_jamba, params_to_hf_jamba,
)
from neuronx_distributed_llama3_2_tpu.models.llama import LlamaAttention, precompute_rope

TINY = JAMBA_CONFIGS["tiny-jamba"]


@pytest.fixture(scope="module")
def params():
    return jax.jit(JambaForCausalLM(TINY).init)(jax.random.key(0))


def test_the_layer_order_follows_period_and_offset():
    published = JAMBA_CONFIGS["jamba2-3b"]
    kinds = published.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [7, 21]
    assert published.layers_of(MAMBA) == 26 and published.d_inner == 5120
    assert published.state_bytes_per_layer() == 5120 * 16 * 4 + 3 * 5120 * 2 == 358_400
    assert TINY.layer_kinds == (MAMBA, ATTENTION, MAMBA, MAMBA, ATTENTION)
    runs = layer_runs(published)
    assert [(r.kind, r.first, r.count, r.layer) for r in runs] == [
        (MAMBA, 0, 7, 0), (ATTENTION, 0, 1, 7), (MAMBA, 7, 13, 8), (ATTENTION, 1, 1, 21), (MAMBA, 20, 6, 22)]
    assert resolve_model("jamba2-3b")["model_cls"] is JambaForCausalLM


def test_the_published_widths_count_three_billion_parameters():
    shapes = jax.eval_shape(JambaForCausalLM(JAMBA_CONFIGS["jamba2-3b"]).init, jax.random.key(0))
    count = lambda t: sum(a.size for a in jax.tree.leaves(t))  # noqa: E731
    assert 41.2e6 < count(shapes["mamba_layers"]["mamba"]) / 26 < 41.3e6
    assert 13.7e6 < count(shapes["attention_layers"]["attention"]) / 2 < 13.8e6
    assert 3.02e9 < count(shapes) < 3.04e9 and "lm_head" not in shapes


def test_what_sets_a_states_memory_is_initialised_as_published(params):
    mamba = params["mamba_layers"][MAMBA]
    n = TINY.mamba_d_state
    np.testing.assert_allclose(jnp.exp(mamba["a_log"][0, :, 0]), np.arange(1, n + 1), rtol=1e-6)
    dt = jax.nn.softplus(mamba["dt_proj"]["bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 1e-1 * 1.001
    assert float(mamba["d_skip"].min()) == float(mamba["d_skip"].max()) == 1.0
    assert mamba["a_log"].dtype == mamba["dt_proj"]["bias"].dtype == jnp.float32


@pytest.mark.parametrize("cut", [1, 3, 16, 20])
def test_a_block_from_a_carried_state_and_tail_equals_the_whole_sequence(params, cut):
    """Rows 0 .. cut, then the rest from what they left behind — a cut inside
    the convolution's reach (1, 3) and past it — against the whole in one."""
    mixer = MambaMixer(TINY)
    lp = jax.tree.map(lambda a: a[1], params["mamba_layers"][MAMBA])
    x = jax.random.normal(jax.random.key(2), (2, 24, TINY.hidden_size))
    u, g = mixer.project(lp, x)
    full = jnp.full((2,), 24, jnp.int32)
    whole, h_whole, tail_whole = mixer.mix(lp, u, g, *mixer.zero_state(2), full)
    a, h, tail = mixer.mix(lp, u[:, :cut], g[:, :cut], *mixer.zero_state(2), full * 0 + cut)
    b, h, tail = mixer.mix(lp, u[:, cut:], g[:, cut:], h, tail, full - cut)
    np.testing.assert_allclose(jnp.concatenate([a, b], axis=1), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h, h_whole, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(tail, tail_whole, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(mixer(lp, x), whole, rtol=1e-6, atol=1e-7)      # the training form


def test_one_token_a_lane_equals_a_block_of_rows_and_padding_is_kept_out(params):
    mixer = MambaMixer(TINY)
    lp = jax.tree.map(lambda a: a[0], params["mamba_layers"][MAMBA])
    x = jax.random.normal(jax.random.key(3), (3, 9, TINY.hidden_size))
    u, g = mixer.project(lp, x)
    whole, h_whole, tail_whole = mixer.mix(lp, u, g, *mixer.zero_state(3), jnp.full((3,), 9, jnp.int32))
    h, tail = mixer.zero_state(3)
    rows = []
    for t in range(9):          # the step form, nine times
        y, h, tail = mixer.mix(lp, u[:, t:t + 1], g[:, t:t + 1], h, tail, jnp.ones((3,), jnp.int32))
        rows.append(y)
    np.testing.assert_allclose(jnp.concatenate(rows, axis=1), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h, h_whole, rtol=1e-5, atol=1e-7)
    # lanes with 9, 5 and 2 real rows in one padded block: state and tail as the unpadded rows leave them
    live = jnp.asarray([9, 5, 2], jnp.int32)
    _, h_pad, tail_pad = mixer.mix(lp, u, g, *mixer.zero_state(3), live)
    for lane, n in enumerate((9, 5, 2)):
        _, h_n, tail_n = mixer.mix(lp, u[lane:lane + 1, :n], g[lane:lane + 1, :n], *mixer.zero_state(1),
                                   jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(h_pad[lane], h_n[0], rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(tail_pad[lane], tail_n[0], rtol=1e-6, atol=1e-8)
    assert float(jnp.abs(h_pad[2] - h_whole[2]).max()) > 1e-6         # blind to the length they would differ


def test_attention_runs_with_no_rotary_table_and_a_table_changes_it(params):
    """20 query heads on one kv head at the published widths is 4 on 1 here;
    ``sin`` None applies nothing, a table rotates q and k."""
    attn = LlamaAttention(TINY)
    lp = jax.tree.map(lambda a: a[0], params["attention_layers"][ATTENTION])
    # large inputs: at this size seeded scores are near zero and every softmax near uniform
    x = 20.0 * jax.random.normal(jax.random.key(4), (2, 12, TINY.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (2, 12))
    bare = attn(lp, x, None, None, positions)
    sin, cos = precompute_rope(TINY.head_dim, 12, 10000.0)
    rotated = attn(lp, x, sin, cos, positions)
    assert bare.shape == rotated.shape == (2, 12, TINY.hidden_size)
    assert float(jnp.abs(bare - rotated).max()) > 0.05 * float(jnp.abs(bare).max())
    # no positional term at all: a causal prefix is unmoved by what follows it
    np.testing.assert_allclose(attn(lp, x[:, :7], None, None, positions[:, :7]), bare[:, :7],
                               rtol=1e-5, atol=1e-5)
    q, k = jnp.ones((1, 2, 4, 16)), jnp.ones((1, 2, 1, 16))
    assert attn._apply_rope(q, k, None, None, positions[:1, :2]) == (q, k)


def test_hf_names_round_trip(params):
    sd = params_to_hf_jamba(params, TINY)
    d, n, r, k = TINY.d_inner, TINY.mamba_d_state, TINY.mamba_dt_rank, TINY.mamba_d_conv
    assert sd["model.layers.0.mamba.in_proj.weight"].shape == (2 * d, TINY.hidden_size)
    assert sd["model.layers.0.mamba.conv1d.weight"].shape == (d, 1, k)
    assert sd["model.layers.0.mamba.x_proj.weight"].shape == (r + 2 * n, d)
    assert sd["model.layers.0.mamba.A_log"].shape == (d, n) and sd["model.layers.0.mamba.D"].shape == (d,)
    assert sd["model.layers.2.mamba.dt_layernorm.weight"].shape == (r,)
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (TINY.head_dim, TINY.hidden_size)
    assert sd["model.layers.4.feed_forward.down_proj.weight"].shape == (TINY.hidden_size, TINY.intermediate_size)
    assert "model.layers.1.mamba.in_proj.weight" not in sd and "lm_head.weight" not in sd
    assert {"model.layers.3.input_layernorm.weight", "model.layers.3.pre_ff_layernorm.weight",
            "model.final_layernorm.weight"} <= set(sd)
    back = params_from_hf_jamba(sd, TINY)
    same = jax.tree.map(lambda a, b: a.dtype == b.dtype and bool((a == b).all()), params, back)
    assert jax.tree.all(same), same
    untied = dataclasses.replace(TINY, tie_word_embeddings=False)
    p2 = jax.jit(JambaForCausalLM(untied).init)(jax.random.key(1))
    assert params_to_hf_jamba(p2, untied)["lm_head.weight"].shape == (TINY.vocab_size, TINY.hidden_size)
