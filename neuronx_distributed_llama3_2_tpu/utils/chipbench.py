"""On-chip micro-timing helpers shared by the A/B stage scripts.

The measurement hazard these exist for: a per-iteration host sync costs a
dispatch and a readback, which can drown few-ms kernel differences.
``time_fn`` chains the calls on-device inside one jitted ``lax.scan`` and
syncs ONCE.

The chain must defeat two XLA optimizations:

- **CSE/elision**: each iteration's output feeds a (numerically
  negligible) data dependency into the next iteration's first argument.
- **dead-code elimination of sibling outputs**: the nudge consumes a
  scalar from EVERY output leaf — ``jax.grad`` with multiple argnums
  returns a tuple, and consuming only the first cotangent would let XLA
  drop the others' backward computation entirely (e.g. the whole dW
  matmul of a fused-CE head timing), silently under-measuring.

Used by scripts/ab_stage.py and scripts/ring_step_bench.py; unit-tested
in tests/test_chipbench.py.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def sync(tree) -> None:
    """One host round-trip on one scalar of ``tree`` (full block)."""
    leaf = jax.tree.leaves(tree)[0]
    np.asarray(jax.device_get(jnp.ravel(leaf)[0]))


def time_fn(fn, *args, repeats: int = 6) -> float:
    """Per-call wall seconds of ``fn(*args)`` with the host round-trip
    amortized over ``repeats`` on-device chained calls."""

    def chained(*a):
        def body(carry, _):
            out = fn(carry, *a[1:])
            # consume one element of EVERY leaf so no output (and no part
            # of the backward that produces it) is dead code
            nudge = jnp.asarray(0.0, jnp.float32)
            for leaf in jax.tree.leaves(out):
                nudge = nudge + jnp.ravel(leaf)[0].astype(jnp.float32)
            return carry + (nudge * 1e-12).astype(a[0].dtype), None

        carry, _ = jax.lax.scan(body, a[0], None, length=repeats)
        return carry

    g = jax.jit(chained)
    sync(g(*args))  # compile + warmup
    t0 = time.perf_counter()
    sync(g(*args))
    return (time.perf_counter() - t0) / repeats
