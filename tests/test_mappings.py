"""Numerical + gradient tests of collective mappings on an 8-device CPU mesh.

Pattern follows the reference's parity harness (parallel vs serial math, error
< 1e-3, test/integration/parallel_layers/test_layers.py:44-82) but runs
hardware-free like its unit tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import mappings, state as ps


def _tp_mesh(tp=4):
    st = ps.initialize_model_parallel(tensor_model_parallel_size=tp)
    return st.mesh


def _shard_map(f, mesh, in_specs, out_specs):
    # check_vma=False: axis_index-based slicing makes values look varying to
    # the static replication checker even when they are mathematically
    # replicated (e.g. after an all-gather); grads remain exact.
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def test_copy_reduce_pair_grads():
    mesh = _tp_mesh(4)
    x = jnp.arange(8.0)

    def body(x):
        y = mappings.copy_to_tensor_model_parallel_region(x)
        # per-rank compute produces tp partial sums
        z = mappings.reduce_from_tensor_model_parallel_region(y * 2.0)
        return z

    f = _shard_map(body, mesh, in_specs=P(), out_specs=P())
    out = f(x)
    np.testing.assert_allclose(out, x * 8.0)  # 2x summed over 4 ranks

    # grad: d/dx sum(z) — copy bwd psums the 4 identical grads then each is 2
    g = jax.grad(lambda x: f(x).sum())(x)
    np.testing.assert_allclose(g, np.full(8, 8.0))


def test_gather_scatter_sequence_parallel_roundtrip():
    mesh = _tp_mesh(4)
    x = jnp.arange(16.0).reshape(16, 1)

    def body(x):
        local = mappings.scatter_to_sequence_parallel_region(x, dim=0)
        assert local.shape == (4, 1)
        full = mappings.gather_from_sequence_parallel_region(local, dim=0)
        return full

    f = _shard_map(body, mesh, in_specs=P(), out_specs=P())
    np.testing.assert_allclose(f(x), x)


def test_reduce_scatter_matches_sum():
    mesh = _tp_mesh(4)
    # replicated input: reduce-scatter should give 4*x shard
    x = jnp.arange(8.0)

    def body(x):
        return mappings.reduce_scatter_to_sequence_parallel_region(x, dim=0)

    f = _shard_map(body, mesh, in_specs=P(), out_specs=P("tp"))
    np.testing.assert_allclose(f(x), x * 4.0)


def test_gather_sp_gradient_is_reduce_scatter():
    mesh = _tp_mesh(4)
    x = jnp.ones((8, 2))

    def loss(x):
        def body(x):
            local = mappings.scatter_to_sequence_parallel_region(x, dim=0)
            full = mappings.gather_from_sequence_parallel_region(local, dim=0)
            return (full**2).sum()

        return _shard_map(
            lambda x: jax.lax.psum(body(x), ps.TP_AXIS) / 4.0, mesh, P(), P()
        )(x)

    g = jax.grad(loss)(x)
    np.testing.assert_allclose(g, 2.0 * x)


def test_all_to_all_expert_parallel_roundtrip():
    st = ps.initialize_model_parallel(
        tensor_model_parallel_size=2, expert_model_parallel_size=2
    )
    mesh = st.mesh
    # per-rank view: full expert dim, tokens sharded over ep
    # (reference mappings.py:412: (e, c, h) -> (e/ep, ep, c, h))
    e, c, h = 4, 6, 2
    x = jnp.arange(float(e * c * h)).reshape(e, c, h)

    def body(x_local):
        y = mappings.enter_expert_parallel_region(x_local)
        assert y.shape == (e // 2, c, h)  # e/ep experts, ep * (c/ep) tokens
        z = mappings.exit_expert_parallel_region(y)
        return z

    f = _shard_map(body, mesh, in_specs=P(None, "ep"), out_specs=P(None, "ep"))
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "ep")))
    np.testing.assert_allclose(np.asarray(f(xs)), np.asarray(x))
