"""High-level training facade.

TPU-native replacement for the reference trainer layer
(``trainer/trainer.py``): ``initialize_parallel_model`` (:141, the 6-phase
meta-device-init → wrap → materialize assembly) collapses to a jit-ed
initializer with output shardings — parameters materialize *directly sharded
on the mesh*, which is the reference's ``meta_device_init`` +
``get_model_sequential`` staged host-RAM dance (model_utils.py:245,320) made
unnecessary. ``make_train_step`` is the canonical train loop body
(tp_zero1_llama_hf_pretrain.py:277-350): microbatched grad accumulation (fp32),
optimizer step, metrics — one compiled XLA program with donated state.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state
from neuronx_distributed_llama3_2_tpu.parallel.layers import constrain
from neuronx_distributed_llama3_2_tpu.parallel.state import DP_AXIS, EP_AXIS
from neuronx_distributed_llama3_2_tpu.trainer.config import TrainingConfig
from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
    OptimizerState,
    apply_gradients,
    init_optimizer_state,
    optimizer_state_specs,
)

BATCH_AXES = (DP_AXIS, EP_AXIS)


class TrainState(NamedTuple):
    params: Any
    opt: OptimizerState


def train_state_specs(model, config: TrainingConfig, params: Any) -> TrainState:
    pspecs = model.specs()
    return TrainState(
        params=pspecs,
        opt=optimizer_state_specs(pspecs, params, config.optimizer),
    )


def _validate_pipeline_config(model, config: TrainingConfig) -> None:
    """Fail loudly when TrainingConfig's pipeline knobs disagree with the
    model actually being trained.

    The schedule lives on PipelinedCausalLM, not on the trainer, so a user
    who sets ``TrainingConfig(pipeline_schedule="interleaved")`` but wraps
    the model with a default-constructed pipeline would otherwise silently
    train under gpipe (ADVICE r3)."""
    model_schedule = getattr(model, "schedule", None)
    model_chunks = getattr(model, "num_model_chunks", None)
    if model_schedule is None:
        # unpipelined model: the config must not ask for a pipeline
        if config.pipeline_schedule is not None or config.num_model_chunks is not None:
            raise ValueError(
                f"TrainingConfig(pipeline_schedule={config.pipeline_schedule!r},"
                f" num_model_chunks={config.num_model_chunks}) but the model is"
                " not pipelined — wrap it in PipelinedCausalLM(schedule=...,"
                " num_model_chunks=...) or leave the config knobs at None"
            )
        return
    if config.pipeline_schedule is not None and model_schedule != config.pipeline_schedule:
        raise ValueError(
            f"model schedule {model_schedule!r} != TrainingConfig."
            f"pipeline_schedule {config.pipeline_schedule!r}"
        )
    if config.num_model_chunks is not None and model_chunks != config.num_model_chunks:
        raise ValueError(
            f"model num_model_chunks {model_chunks} != TrainingConfig."
            f"num_model_chunks {config.num_model_chunks}"
        )


def initialize_parallel_model(
    model,
    config: TrainingConfig,
    key: Optional[jax.Array] = None,
) -> Tuple[TrainState, TrainState]:
    """Build a fully sharded TrainState. Returns (state, state_specs).

    The init function is jit-compiled with ``out_shardings`` derived from the
    model's spec tree, so each device only ever materializes its own shard —
    the reference needs meta-device init + sequential materialization
    (trainer/trainer.py:141-229, model_utils.py:320) to avoid host OOM; here
    XLA never builds the unsharded model anywhere.
    """
    _validate_pipeline_config(model, config)
    if key is None:
        key = jax.random.key(config.seed)
    mesh = parallel_state.get_parallel_state().mesh

    def init_fn(key):
        params = model.init(key)
        opt = init_optimizer_state(params, config.optimizer)
        return TrainState(params=params, opt=opt)

    abstract = jax.eval_shape(init_fn, key)
    specs = TrainState(
        params=model.specs(),
        opt=optimizer_state_specs(
            model.specs(), abstract.params, config.optimizer
        ),
    )
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )
    state = jax.jit(init_fn, out_shardings=shardings)(key)
    return state, specs


def default_weight_decay_mask(params: Any) -> Any:
    """True where weight decay applies: skip norms scales and biases
    (the reference examples' two param groups,
    tp_zero1_llama_hf_pretrain.py optimizer_grouped_parameters pattern)."""

    def decide(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        joined = "/".join(str(k) for k in keys).lower()
        if "norm" in joined or "bias" in joined or "scale" in joined:
            return False
        return leaf.ndim >= 2

    return jax.tree_util.tree_map_with_path(decide, params)


def make_train_step(
    model,
    config: TrainingConfig,
) -> Callable:
    """Compiled train step: (state, batch) -> (state, metrics).

    batch = {"input_ids": (GBS, S) int32, "labels": (GBS, S) int32}; GBS is
    split into ``config.num_microbatches`` sequential microbatches whose
    gradients accumulate in fp32 (reference grad-accum loop +
    use_fp32_grad_acc, tp_zero1_llama_hf_pretrain.py:277-350). The whole step
    is ONE XLA program — no per-microbatch graph breaks (the reference pays a
    mark_step per accumulation step).
    """
    _validate_pipeline_config(model, config)
    opt_cfg = config.optimizer
    n_micro = config.num_microbatches

    def loss_fn(params, input_ids, labels):
        return model.loss(params, input_ids, labels)

    # a model exposing loss_and_grad computes its own gradients (the 1F1B /
    # memory-bounded-interleaved pipelines interleave fwd/bwd manually —
    # autodiff can't express their schedules); otherwise differentiate
    if hasattr(model, "loss_and_grad") and getattr(
        model, "uses_manual_vjp", getattr(model, "schedule", None) == "1f1b"
    ):
        grad_fn = lambda p, ids, lbl: model.loss_and_grad(p, ids, lbl)  # noqa: E731
    else:
        grad_fn = jax.value_and_grad(loss_fn)

    # every instruction of the program carries `train_step` as the first
    # segment of its op_name path (serving/tracing.py PROGRAM_SCOPES); the
    # blocks' own scopes (attn, mlp, ce, optimizer, ...) nest under it
    @jax.named_scope("train_step")
    def train_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        input_ids, labels = batch["input_ids"], batch["labels"]
        input_ids = jax.lax.with_sharding_constraint(
            input_ids,
            NamedSharding(
                parallel_state.get_parallel_state().mesh, P(BATCH_AXES, None)
            ),
        )
        if n_micro == 1:
            loss, grads = grad_fn(state.params, input_ids, labels)
            if opt_cfg.use_fp32_grad_acc:
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            gbs = input_ids.shape[0]
            mbs = gbs // n_micro
            # strided split (row m of microbatch k = global row k + m*n_micro)
            # so every dp shard's contiguous rows contribute to every
            # microbatch — a contiguous reshape would concentrate each
            # microbatch on a dp subset and force a resharding all-to-all
            mb_ids = input_ids.reshape(mbs, n_micro, -1).swapaxes(0, 1)
            mb_lbl = labels.reshape(mbs, n_micro, -1).swapaxes(0, 1)
            acc_dtype = jnp.float32 if opt_cfg.use_fp32_grad_acc else None
            vocab = getattr(model.config, "vocab_size", None)

            def valid_count(lbl):
                # same validity rule as the CE kernel (shifted labels), via
                # the shared single source of truth
                from neuronx_distributed_llama3_2_tpu.parallel.loss import (
                    valid_token_mask,
                )

                shifted = lbl[:, 1:]
                ok = (
                    valid_token_mask(shifted, vocab)
                    if vocab is not None
                    else shifted >= 0
                )
                return jnp.sum(ok.astype(jnp.float32))

            def micro(carry, mb):
                # weight each microbatch's masked-mean loss/grads by its
                # valid-token count so the accumulated step equals the
                # global-batch mean CE even when padding is uneven across
                # microbatches (advisor finding on equal-weight averaging)
                acc, loss_acc, tok_acc = carry
                ids, lbl = mb
                loss, grads = grad_fn(state.params, ids, lbl)
                n = valid_count(lbl)
                acc = jax.tree.map(
                    lambda a, g: a + (g.astype(a.dtype) * n.astype(a.dtype)),
                    acc, grads,
                )
                return (acc, loss_acc + loss * n, tok_acc + n), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(
                    p.shape, acc_dtype or p.dtype
                ),
                state.params,
            )
            (grads, loss_sum, tok_sum), _ = jax.lax.scan(
                micro, (zero, jnp.float32(0), jnp.float32(0)), (mb_ids, mb_lbl)
            )
            denom = jnp.maximum(tok_sum, 1.0)
            grads = jax.tree.map(lambda g: g / denom.astype(g.dtype), grads)
            loss = loss_sum / denom

        new_params, new_opt, grad_norm = apply_gradients(
            state.opt,
            grads,
            state.params,
            opt_cfg,
            weight_decay_mask=default_weight_decay_mask(state.params),
        )
        # pin the output state to its canonical specs: keeps shardings
        # identical step over step (no drift-induced recompiles) and gives
        # XLA's partitioner an anchor when grads come out of manual shard_map
        # regions (the 1F1B executor + ZeRO combination trips a partitioner
        # CHECK without this)
        pspecs = model.specs()
        new_params = jax.tree.map(constrain, new_params, pspecs)
        new_opt = jax.tree.map(
            constrain, new_opt,
            optimizer_state_specs(pspecs, state.params, opt_cfg),
        )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "grad_norm": grad_norm,
            "learning_rate": opt_cfg.lr_at(new_opt.step),
            "step": new_opt.step,
        }
        return TrainState(params=new_params, opt=new_opt), metrics

    step = jax.jit(train_step, donate_argnums=0)
    counters = getattr(model, "traced_counters", None)
    return step if counters is None else _StepWithCounters(step, counters)


class _StepWithCounters:
    """The jitted step of a pipelined model, with the schedule's counters
    (``rotations``, ``useful_lane_rotations``, ``stage_forwards_per_slot``,
    ``residual_ring_bytes``: Python ints, no device work)
    merged into the metrics it returns. Everything else — ``lower``,
    ``trace``, ``_cache_size`` — is the jitted function's own."""

    def __init__(self, step, counters: Callable[[], dict]):
        self._step = step
        self._read = counters   # reads the mesh, so not before the first step
        self._counters: Optional[dict] = None

    def __call__(self, state, batch):
        state, metrics = self._step(state, batch)
        if self._counters is None:
            self._counters = self._read()
        return state, {**metrics, **self._counters}

    def __getattr__(self, name):
        return getattr(self._step, name)


def make_eval_step(model, config: TrainingConfig) -> Callable:
    """Compiled evaluation step: (params, batch) -> loss (fp32 scalar).

    The role of the reference's ``run_eval`` / ``InferenceSchedule`` path
    (pipeline/model.py:790, scheduler.py:144): the same loss as training
    with no gradients, no optimizer, and no microbatching (one forward over
    the global batch; the pipelined model does its own microbatch rotation
    inside ``loss``). Works with every model exposing the causal-LM
    ``loss(params, input_ids, labels)`` protocol, including
    :class:`~..pipeline.PipelinedCausalLM`.
    """

    def eval_step(params, batch):
        input_ids, labels = batch["input_ids"], batch["labels"]
        input_ids = jax.lax.with_sharding_constraint(
            input_ids,
            NamedSharding(
                parallel_state.get_parallel_state().mesh, P(BATCH_AXES, None)
            ),
        )
        return model.loss(params, input_ids, labels).astype(jnp.float32)

    return jax.jit(eval_step)


def evaluate(
    model, config: TrainingConfig, params, batches, eval_step=None
) -> float:
    """Mean eval loss over an iterable of batches (the reference's eval
    loop around run_eval). Pass a prebuilt ``eval_step`` (from
    :func:`make_eval_step`) when calling repeatedly — a fresh jit wrapper
    per call would recompile the eval program every interval."""
    step = eval_step if eval_step is not None else make_eval_step(model, config)
    total, n = 0.0, 0
    for batch in batches:
        total += float(step(params, batch))
        n += 1
    if n == 0:
        raise ValueError("evaluate() got an empty batch iterable")
    return total / n
