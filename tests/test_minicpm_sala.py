"""MiniCPM-SALA's model module at the tiny size, float32 on the CPU: the
training-shaped forward against the benchmark's plain reference on every row,
the Lightning layer's chunk form against its step form, what the selection
forces and drops, and the configuration's own rules."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec
from neuronx_distributed_llama3_2_tpu.models import model_registry
from neuronx_distributed_llama3_2_tpu.models.minicpm_sala import (
    LIGHTNING, PUBLISHED_MIXERS, SALA_CONFIGS, SPARSE, SalaConfig, SalaForCausalLM, block_mask,
    layer_runs, lightning_chunk, lightning_slopes, lightning_step, select_blocks, whole_kernels,
)

TINY = SALA_CONFIGS["tiny-sala"]
TOL = 1e-4


@pytest.fixture(scope="module")
def fam():
    fam = spec.load_family("minicpm_sala")
    fam.model_config({"rehearsal": {"preset": "tiny-sala"}}, True)
    return fam


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every kernel and the embedding five times as large
    (see ``tests/test_jamba_serving.py``): scaled, a wrong block or a lost
    state moves the logits by percent."""
    params = jax.jit(SalaForCausalLM(TINY).init)(jax.random.key(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * 5.0 if path[-1].key in ("kernel", "embedding", "gate_up")
        or path[-1].key.endswith("_kernel") else a, params)


def test_the_published_preset_and_the_registry():
    c = SALA_CONFIGS["minicpm-sala"]
    assert (c.hidden_size, c.intermediate_size, c.vocab_size, c.num_layers) == (4096, 16384, 73448, 32)
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.lightning_heads) == (32, 2, 128, 32)
    assert c.mixer_types == PUBLISHED_MIXERS and c.layers_of(SPARSE) == 8 and c.layers_of(LIGHTNING) == 24
    assert [i for i, k in enumerate(c.mixer_types) if k == SPARSE] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert not c.tie_word_embeddings and c.rms_norm_eps == 1e-6 and c.rope_theta == 10000.0
    assert c.residual_scale == pytest.approx(1.4 / 32 ** 0.5) and c.logit_divisor == 16.0 and c.scale_emb == 12.0
    assert (c.kernel_size, c.kernel_stride, c.sparse_block_size, c.sparse_topk) == (32, 16, 64, 64)
    assert (c.sparse_init_blocks, c.sparse_window, c.kernels_per_block) == (1, 2048, 4)
    assert c.state_bytes_per_layer() == 32 * 128 * 128 * 4
    assert model_registry()["minicpm-sala"]["model_cls"] is SalaForCausalLM
    # the cell's cut: the one run of eight with the published ratio, as runs of one kind
    cut = dataclasses.replace(c, num_layers=8, mixer_types=c.mixer_types[9:17])
    assert [(r.kind, r.count, r.kind_first) for r in layer_runs(cut)] == [
        (SPARSE, 1, 0), (LIGHTNING, 6, 0), (SPARSE, 1, 1)]


@pytest.mark.parametrize("changes,word", [
    ({"mixer_types": (SPARSE,) * 4}, "mixer_types names 4 layers"),
    ({"mixer_types": (SPARSE, "mamba", SPARSE, SPARSE, SPARSE)}, "of kinds"),
    ({"kernel_size": 5}, "whole strides"),
])
def test_a_config_that_names_no_such_stack_is_refused(changes, word):
    with pytest.raises(ValueError, match=word):
        dataclasses.replace(TINY, **changes)


def test_the_model_matches_the_reference_on_every_row(fam, params):
    """100 rows: 25 blocks of 4 behind the last one, of which a row reads 6 —
    the first, its window's two or three, and the best-scoring of the rest."""
    ids = jax.random.randint(jax.random.key(1), (2, 100), 1, TINY.vocab_size)
    got = jax.jit(SalaForCausalLM(TINY).__call__)(params, ids)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, i: fam.reference.forward_logits(p, fam.reference_config(TINY), i))(params, ids)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    # and the selection does drop blocks there: with it ignored the late rows move
    dense = dataclasses.replace(TINY, sparse_topk=25)
    all_rows = jax.jit(SalaForCausalLM(dense).__call__)(params, ids)
    moved = np.linalg.norm(all_rows - got, axis=-1) / np.linalg.norm(got, axis=-1)
    assert moved[:, :24].max() < TOL and moved[:, 40:].max() > 30 * TOL


@pytest.mark.parametrize("pieces", [(16, 16, 8), (7, 33), (40,)], ids=["16-16-8", "7-33", "whole"])
def test_the_chunk_form_is_the_step_form(pieces):
    """A sequence cut into chunks — each padded by three rows that ``live``
    leaves out — against one row after another from the zero state."""
    n, d, total = 4, 16, sum(pieces)
    q, k, v = (jax.random.normal(jax.random.key(i), (2, total, n, d)) for i in range(3))
    slopes = lightning_slopes(n)
    state, rows = jnp.zeros((2, n, d, d)), []
    for t in range(total):
        o, state = lightning_step(q[:, t], k[:, t], v[:, t], state, jnp.ones((2,), bool), slopes)
        rows.append(o)
    want = jnp.stack(rows, axis=1)
    chunked, got, start = jnp.zeros((2, n, d, d)), [], 0
    for size in pieces:
        pad = lambda a: jnp.pad(a[:, start:start + size], ((0, 0), (0, 3), (0, 0), (0, 0)), constant_values=9.0)  # noqa: E731
        o, chunked = lightning_chunk(pad(q), pad(k), pad(v), chunked, jnp.full((2,), size), slopes)
        got.append(o[:, :size])
        start += size
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, rtol=TOL, atol=TOL)
    np.testing.assert_allclose(chunked, state, rtol=TOL, atol=TOL)
    # a lane that is not alive keeps its state
    _, kept = lightning_step(q[:, 0], k[:, 0], v[:, 0], state, jnp.asarray([True, False]), slopes)
    assert bool((kept[1] == state[1]).all()) and not bool((kept[0] == state[0]).all())


def test_the_decay_is_lightning_attention_2s():
    slopes = np.asarray(lightning_slopes(32))
    np.testing.assert_allclose(slopes, 2.0 ** (-8.0 * np.arange(1, 33) / 32), rtol=1e-6)
    assert slopes[0] == pytest.approx(2 ** -0.25) and slopes[-1] == pytest.approx(2 ** -8)


def test_the_selection_forces_the_first_block_and_the_window_and_fills_by_score():
    c = TINY                                    # blocks of 4, 6 a row, window 6, kernels of 4 every 2
    s, blocks = 64, 16
    q = jax.random.normal(jax.random.key(0), (1, s, c.num_heads, c.head_dim))
    k = jax.random.normal(jax.random.key(1), (1, s, c.num_kv_heads, c.head_dim))
    pos = jnp.arange(s)[None]
    chosen, taken = select_blocks(q, whole_kernels(k, blocks, c), pos, c)
    assert chosen.shape == taken.shape == (1, s, c.num_kv_heads, 6)
    mask = np.asarray(block_mask(chosen, taken, blocks))[0]                  # (s, nkv, blocks)
    for p in (0, 3, 22, 23, 41, 63):
        own, first = p // 4, max(p - 5, 0) // 4
        forced = {0, *range(first, own + 1)}
        for g in range(c.num_kv_heads):
            got = set(np.flatnonzero(mask[p, g]))
            assert forced <= got and max(got) <= own and len(got) == min(own + 1, 6), (p, g, got)
    # the rest by score: the two kv groups choose differently somewhere
    assert (mask[:, 0] != mask[:, 1]).any()
    # a block with a larger score wins: the key rows of block 2 made the query's own direction
    lead = q[0, 63].reshape(c.num_kv_heads, -1, c.head_dim).mean(axis=1)
    k_hot = k.at[0, 8:12].set(10.0 * lead[None])
    hot, hot_taken = select_blocks(q, whole_kernels(k_hot, blocks, c), pos, c)
    assert all(2 in set(np.asarray(hot[0, 63, g])[np.asarray(hot_taken[0, 63, g])]) for g in range(2))
