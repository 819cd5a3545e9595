"""AdamW with fp32 master weights + ZeRO-1 state sharding.

TPU-native replacement for the reference's optimizer stack:

- ``AdamW_FP32OptimParams`` (utils/adamw_fp32_optim_params.py:31): fp32 master
  copies of bf16 params inside the optimizer state. Here ``OptimizerState.master``
  holds the fp32 truth; params are its bf16 cast.
- ``NeuronZero1Optimizer`` (optimizer/zero_redundancy_optimizer.py:29):
  optimizer-state sharding over the DP group. The reference needs a whole
  class (per-rank shard bookkeeping, grad reduce-scatter, param all-gather,
  custom save/load); under GSPMD it is *only a PartitionSpec*: master/mu/nu
  get an extra dp-sharding on a free dimension and XLA inserts the
  reduce-scatter/all-gather around the update
  (:func:`optimizer_state_specs`).
- ``NxDOptimizer.step`` choreography (trainer/optimizer.py:116): SP/DP grad
  reductions happen automatically from sharding; what remains is clip →
  AdamW → cast-down, in :func:`apply_gradients`.
- EP awareness (``NeuronEPZero1Optimizer`` zero_redundancy_optimizer.py:158):
  params whose spec mentions the ep axis get their state dp-sharded over
  ("dp",) only — the expert-DP group (parallel_state.py:86-95).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.grads import clip_grad_norm
from neuronx_distributed_llama3_2_tpu.parallel.state import DP_AXIS, EP_AXIS
from neuronx_distributed_llama3_2_tpu.trainer.config import OptimizerConfig


class OptimizerState(NamedTuple):
    step: jax.Array  # scalar int32
    master: Any  # fp32 master params (None when use_master_weights=False)
    mu: Any  # fp32 first moment
    nu: Any  # fp32 second moment


def init_optimizer_state(params: Any, config: OptimizerConfig) -> OptimizerState:
    sd = jnp.dtype(config.state_dtype)
    cast = lambda t: jax.tree.map(lambda p: p.astype(sd), t)
    zeros = lambda t: jax.tree.map(lambda p: jnp.zeros(p.shape, sd), t)
    return OptimizerState(
        step=jnp.zeros((), jnp.int32),
        master=cast(params) if config.use_master_weights else None,
        mu=zeros(params),
        nu=zeros(params),
    )


def _zero1_leaf_spec(spec: P, shape, dp_axes) -> P:
    """Add dp-sharding on the first free, divisible dim of one state leaf."""
    dp_size = 1
    mesh = parallel_state.get_parallel_state().mesh
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    if dp_size == 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, dim) in enumerate(zip(parts, shape)):
        if p is None and dim % dp_size == 0:
            parts[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            return P(*parts)
    return spec  # no dp-shardable dim; state stays replicated over dp


def _spec_mentions(spec: P, axis: str) -> bool:
    for p in spec:
        if p == axis or (isinstance(p, tuple) and axis in p):
            return True
    return False


def optimizer_state_specs(
    param_specs: Any, params: Any, config: OptimizerConfig
) -> OptimizerState:
    """PartitionSpec tree for :class:`OptimizerState`.

    With ``zero_one_enabled`` each fp32 state leaf is additionally sharded
    over the DP axes — ("dp","ep") for dense params, ("dp",) for expert
    params (the reference's sharding_groups=DP / expert-DP split,
    trainer/trainer.py:232-283)."""
    if config.zero_one_enabled:
        is_p = lambda s: isinstance(s, P)
        state_specs = jax.tree.map(
            lambda s, p: _zero1_leaf_spec(
                s,
                p.shape,
                (DP_AXIS,) if _spec_mentions(s, EP_AXIS) else (DP_AXIS, EP_AXIS),
            ),
            param_specs,
            params,
            is_leaf=is_p,
        )
    else:
        state_specs = param_specs
    return OptimizerState(
        step=P(),
        master=state_specs if config.use_master_weights else None,
        mu=state_specs,
        nu=state_specs,
    )


@jax.named_scope("optimizer")
def apply_gradients(
    state: OptimizerState,
    grads: Any,
    params: Any,
    config: OptimizerConfig,
    weight_decay_mask: Any = None,
) -> Tuple[Any, OptimizerState, jax.Array]:
    """One AdamW step. Returns (new_params, new_state, pre-clip grad norm).

    Order follows the reference NxDOptimizer.step (trainer/optimizer.py:116):
    [grad reductions — implicit under GSPMD] → clip by global norm
    (grads.py:180) → AdamW in fp32 → params = cast(master)."""
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    if config.grad_clipping:
        grads, grad_norm = clip_grad_norm(grads, config.max_grad_norm)
    else:
        from neuronx_distributed_llama3_2_tpu.parallel.grads import global_norm

        grad_norm = global_norm(grads)

    step = state.step + 1
    lr = config.lr_at(step)
    b1, b2 = config.beta1, config.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    sd = jnp.dtype(config.state_dtype)
    # moment math in fp32 regardless of storage dtype
    mu = jax.tree.map(
        lambda m, g: (b1 * m.astype(jnp.float32) + (1 - b1) * g).astype(sd),
        state.mu, grads,
    )
    nu = jax.tree.map(
        lambda v, g: (b2 * v.astype(jnp.float32) + (1 - b2) * g * g).astype(sd),
        state.nu, grads,
    )

    current = jax.tree.map(
        lambda p: p.astype(jnp.float32),
        state.master if config.use_master_weights else params,
    )

    if weight_decay_mask is None:
        weight_decay_mask = jax.tree.map(lambda _: True, current)

    def upd(p32, m, v, wd_on):
        mhat = m.astype(jnp.float32) / c1
        vhat = v.astype(jnp.float32) / c2
        wd = config.weight_decay if wd_on else 0.0
        return p32 - lr * (mhat / (jnp.sqrt(vhat) + config.eps) + wd * p32)

    new_master = jax.tree.map(upd, current, mu, nu, weight_decay_mask)
    new_params = jax.tree.map(
        lambda p, m: m.astype(p.dtype), params, new_master
    )
    new_state = OptimizerState(
        step=step,
        master=jax.tree.map(lambda m: m.astype(sd), new_master)
        if config.use_master_weights
        else None,
        mu=mu,
        nu=nu,
    )
    return new_params, new_state, grad_norm
