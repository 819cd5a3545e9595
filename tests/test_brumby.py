"""Power retention (models/brumby.py) at the tiny size, float32 on the CPU:
the feature map, the three forms against each other, the training-side model
against the plain reference (logits, loss and gradients), the HF name map."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import arith_retention, spec
from neuronx_distributed_llama3_2_tpu.models import brumby, model_registry
from neuronx_distributed_llama3_2_tpu.models.brumby import (
    BRUMBY_CONFIGS, BrumbyForCausalLM, feature_width, power_features, retention_chunks, retention_step,
)

TINY = BRUMBY_CONFIGS["tiny-brumby"]
TOL = 1e-4
T, K, G, D_HEAD = 40, 2, 2, 32


@pytest.fixture(scope="module")
def fam():
    return spec.load_family("brumby")


@pytest.fixture(scope="module")
def params():
    return jax.jit(BrumbyForCausalLM(TINY).init)(jax.random.key(0))


@pytest.fixture(scope="module")
def rows():
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (T, K, G, D_HEAD))
    k = jax.random.normal(ks[1], (T, K, D_HEAD))
    v = jax.random.normal(ks[2], (T, K, D_HEAD))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (T, K)) + 2.0)     # gates near 0.9: a long memory
    return q, k, v, log_g


def attention_form(q, k, v, log_g, eps=1e-6):
    """y_i = sum_j A_ij v_j / (sum_j A_ij + eps), A_ij = (q_i.k_j/sqrt(d))^2 exp(sum_{j<m<=i} log g_m)."""
    decay = jnp.cumsum(log_g, axis=0).T                                       # (K, T)
    a = jnp.square(jnp.einsum("ikgd,jkd->kgij", q, k) / np.sqrt(q.shape[-1]))
    a = a * jnp.exp(decay[:, :, None] - decay[:, None, :])[:, None]
    a = jnp.where(jnp.tril(jnp.ones((q.shape[0],) * 2, bool)), a, 0.0)
    return jnp.einsum("kgij,jkv->ikgv", a, v) / (a.sum(-1).transpose(2, 0, 1)[..., None] + eps)


def zero_state():
    width = feature_width(D_HEAD)
    return jnp.zeros((K, width, D_HEAD)), jnp.zeros((K, width))


@pytest.mark.parametrize("d", [16, 32, 128])
def test_phi_squares_the_dot_product(d):
    a, b = jax.random.normal(jax.random.key(1), (2, 5, d))
    got = jnp.sum(power_features(a) * power_features(b), axis=-1)
    np.testing.assert_allclose(got, jnp.sum(a * b, axis=-1) ** 2, rtol=1e-4, atol=1e-3)
    blocks = d // brumby.PHI_BLOCK
    assert power_features(a).shape[-1] == feature_width(d) == blocks * (blocks + 1) // 2 * 256


def test_the_published_width_and_the_benchmarks_arithmetic():
    big = BRUMBY_CONFIGS["brumby-14b"]
    assert big.feature_width == 9216 and arith_retention.narrowest_feature_width(128) == 8256
    assert big.state_bytes_per_layer() == arith_retention.state_bytes(8, 128, 9216) == 38_043_648
    assert arith_retention.CHUNK_ROWS == brumby.RETENTION_CHUNK
    with pytest.raises(ValueError, match="multiple of 16"):
        feature_width(24)


@pytest.mark.parametrize("chunk", [1, 7, 16, 512], ids=["chunk1", "chunk7", "chunk16", "whole"])
def test_chunked_form_equals_attention_form(rows, chunk, monkeypatch):
    monkeypatch.setattr(brumby, "RETENTION_CHUNK", chunk)
    y, _, _ = retention_chunks(*zero_state(), *rows, T, 1e-6)
    np.testing.assert_allclose(y, attention_form(*rows), rtol=TOL, atol=TOL)


def test_recurrent_form_equals_attention_form_and_the_chunked_state(rows):
    q, k, v, log_g = rows
    state, z = zero_state()
    ys = []
    for i in range(T):
        y, state, z = retention_step(state, z, q[i], k[i], v[i], log_g[i], 1e-6)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys), attention_form(*rows), rtol=TOL, atol=TOL)
    _, s_chunked, z_chunked = retention_chunks(*zero_state(), *rows, T, 1e-6)
    np.testing.assert_allclose(state, s_chunked, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(z, z_chunked, rtol=TOL, atol=TOL)


def test_a_chunk_continues_a_state_and_dead_rows_leave_it_alone(rows, monkeypatch):
    """The first 24 rows, then the rest from their state, give the whole; rows
    at or past the live length change neither the outputs before them nor the
    state, whatever they hold."""
    monkeypatch.setattr(brumby, "RETENTION_CHUNK", 16)
    q, k, v, log_g = rows
    want, s_want, z_want = retention_chunks(*zero_state(), *rows, T, 1e-6)
    y1, s1, z1 = retention_chunks(*zero_state(), q[:24], k[:24], v[:24], log_g[:24], 24, 1e-6)
    pad = lambda a, fill: jnp.concatenate([a[24:], jnp.full((8,) + a.shape[1:], fill, a.dtype)])  # noqa: E731
    y2, s2, z2 = retention_chunks(s1, z1, pad(q, 3.0), pad(k, 5.0), pad(v, 7.0), pad(log_g, -1.0), T - 24, 1e-6)
    np.testing.assert_allclose(jnp.concatenate([y1, y2[:T - 24]]), want, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(s2, s_want, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(z2, z_want, rtol=TOL, atol=TOL)


def test_the_state_goes_between_inner_chunks_in_the_dtype_it_came_in(rows, monkeypatch):
    """A bfloat16 state is rounded at every inner boundary, not only where the
    call ends: the rows of the first inner chunk read no state and equal the
    float32 run's, every later row differs (the benchmark's check tells a
    bfloat16 pool from a float32 one by rows inside its first 512-row piece)."""
    monkeypatch.setattr(brumby, "RETENTION_CHUNK", 16)
    want, s_want, _ = retention_chunks(*zero_state(), *rows, T, 1e-6)
    low = [a.astype(jnp.bfloat16) for a in zero_state()]
    got, s_got, z_got = retention_chunks(*low, *rows, T, 1e-6)
    assert s_got.dtype == z_got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got[:16], want[:16])
    gap = np.abs(np.asarray(got[16:] - want[16:])).reshape(T - 16, -1).max(axis=1)
    assert (gap > 0).all() and gap.max() < 0.05
    np.testing.assert_allclose(s_got.astype(jnp.float32), s_want, rtol=0.05, atol=0.05)


def test_model_logits_match_the_reference(fam, params):
    ids = jax.random.randint(jax.random.key(1), (2, 50), 1, TINY.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = fam.reference.forward_logits(params, fam.reference_config(TINY), ids)
        got = BrumbyForCausalLM(TINY)(params, ids)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_the_reference_takes_queries_in_blocks(fam, params, monkeypatch):
    ids = jax.random.randint(jax.random.key(2), (1, 45), 1, TINY.vocab_size)
    cfg = fam.reference_config(TINY)
    whole = fam.reference.forward_logits(params, cfg, ids)
    monkeypatch.setattr(fam.reference, "QUERY_ROWS", 16)          # 45 rows: two blocks and an overlapping tail
    np.testing.assert_allclose(fam.reference.forward_logits(params, cfg, ids), whole, rtol=TOL, atol=TOL)


def test_model_loss_and_gradients_match_the_reference(fam, params):
    ids = jax.random.randint(jax.random.key(3), (2, 33), 1, TINY.vocab_size)
    cfg = fam.reference_config(TINY)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(lambda p: BrumbyForCausalLM(TINY).loss(p, ids, ids))(params)
        want, g_want = jax.value_and_grad(lambda p: fam.reference.loss(p, cfg, ids))(params)
    np.testing.assert_allclose(got, want, rtol=TOL)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(g_got["layers"]["attn"]["gate"]["kernel"]).max()) > 0


def test_remat_and_a_layer_loop_change_nothing(params):
    ids = jax.random.randint(jax.random.key(4), (1, 20), 1, TINY.vocab_size)
    want = BrumbyForCausalLM(TINY)(params, ids)
    for change in ({"scan_layers": False}, {"remat": "full"}):
        got = BrumbyForCausalLM(dataclasses.replace(TINY, **change))(params, ids)
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_hf_names_round_trip(params):
    entry = model_registry()["tiny-brumby"]
    assert entry["model_cls"] is BrumbyForCausalLM
    hf = entry["to_hf"](params, TINY)
    assert hf["model.layers.1.self_attn.g_proj.weight"].shape == (TINY.num_kv_heads, TINY.hidden_size)
    assert hf["model.layers.0.self_attn.q_norm.weight"].shape == (TINY.head_dim,)
    back = entry["from_hf"](hf, TINY)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert jax.tree.structure(back) == jax.tree.structure(params)


def test_specs_cover_the_parameters(params):
    specs = BrumbyForCausalLM(TINY).specs()
    assert jax.tree.structure(jax.tree.map(lambda _: 0, specs, is_leaf=lambda s: not isinstance(s, dict))) \
        == jax.tree.structure(jax.tree.map(lambda _: 0, params))
