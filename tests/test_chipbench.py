"""utils/chipbench.py: the chained on-device timer."""


def test_chipbench_time_fn_consumes_all_grad_outputs():
    """The shared timer must keep EVERY output leaf live: jax.grad with
    multiple argnums returns sibling cotangents, and consuming only the
    first would let XLA dead-code the others' backward (under-measuring,
    e.g., the whole dW matmul of a head timing). Verify by checking the
    compiled chained program's flop count grows when a second cotangent
    is present."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_llama3_2_tpu.utils.chipbench import time_fn

    def loss(h, w):
        return jnp.sum((h @ w) ** 2)

    h = jnp.ones((64, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32)

    def cost_of(fn):
        def chained(*a):
            def body(carry, _):
                out = fn(carry, *a[1:])
                nudge = jnp.asarray(0.0, jnp.float32)
                for leaf in jax.tree.leaves(out):
                    nudge = nudge + jnp.ravel(leaf)[0]
                return carry + (nudge * 1e-12).astype(a[0].dtype), None

            carry, _ = jax.lax.scan(body, a[0], None, length=4)
            return carry

        return jax.jit(chained).lower(h, w).compile().cost_analysis()["flops"]

    both = cost_of(jax.grad(loss, argnums=(0, 1)))
    just_h = cost_of(jax.grad(loss, argnums=(0,)))
    assert both > just_h * 1.3, (both, just_h)  # dW backward stayed live

    # and the public helper runs + returns a sane duration
    dt = time_fn(jax.grad(loss, argnums=(0, 1)), h, w, repeats=2)
    assert 0 < dt < 60
