"""Vocab-parallel CE vs dense CE (reference tolerance pattern
test/integration/parallel_layers test_loss_functions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import loss as L, state as ps


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_parallel_xent_matches_dense(smoothing):
    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    mesh = st.mesh
    B, S, V = 2, 8, 64
    k = jax.random.PRNGKey(0)
    logits = jax.random.normal(k, (B, S, V)) * 3.0
    labels = jax.random.randint(jax.random.fold_in(k, 1), (B, S), 0, V)

    dense = L.cross_entropy(logits, labels, smoothing)
    logits_s = jax.device_put(logits, NamedSharding(mesh, P(None, None, "tp")))
    with jax.sharding.set_mesh(mesh):
        par = jax.jit(lambda lg, lb: L.parallel_cross_entropy(lg, lb, smoothing))(
            logits_s, labels
        )
    np.testing.assert_allclose(np.asarray(par), np.asarray(dense), rtol=1e-5, atol=1e-6)


def test_parallel_xent_grad_matches_dense():
    st = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    mesh = st.mesh
    B, V = 4, 32
    k = jax.random.PRNGKey(2)
    logits = jax.random.normal(k, (B, V))
    labels = jax.random.randint(jax.random.fold_in(k, 3), (B,), 0, V)

    gd = jax.grad(lambda lg: L.cross_entropy(lg, labels).mean())(logits)
    logits_s = jax.device_put(logits, NamedSharding(mesh, P(None, "tp")))
    with jax.sharding.set_mesh(mesh):
        gp = jax.jit(
            jax.grad(lambda lg: L.parallel_cross_entropy(lg, labels).mean())
        )(logits_s)
    # softmax - onehot backward (reference loss_functions.py:103)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), rtol=1e-5, atol=1e-6)


def test_xent_sanity_perfect_prediction():
    logits = jnp.full((1, 4), -20.0).at[0, 2].set(20.0)
    labels = jnp.array([2])
    assert float(L.cross_entropy(logits, labels)[0]) < 1e-5
