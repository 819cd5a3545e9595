"""Training cells: the program's ``TrainingConfig`` -> ``initialize_parallel_model``
-> ``make_train_step`` (through ``PipelinedCausalLM`` when the layout has
pipeline stages), fed a fresh seeded batch every step.

The benchmark passes the layout, the batch and what a user's launch line
always states (``use_flash_attention``, sequence parallelism — written in the
traffic file under ``"launch"``). Remat policy, flash tile sizes, the loss
chunk and the optimizer stay as the program ships them.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmarks import check, profile, stats, traffic as traffic_mod

# step indices of the check and warm-up batches: far from any measured step's
WARMUP_STREAM = 1 << 30


def build_trainer(cell, family, devices, rehearsal: bool):
    """(model, training config, jitted step, model config) on ``devices`` —
    real ones, or described ones for an ahead-of-time compile."""
    from neuronx_distributed_llama3_2_tpu.pipeline import PipelinedCausalLM
    from neuronx_distributed_llama3_2_tpu.trainer import (
        TrainingConfig, make_train_step,
    )

    job = cell.traffic
    layout = job["layout"]
    tp, pp = int(layout.get("tp", 1)), int(layout.get("pp", 1))
    launch = job.get("launch", {})
    model_cfg = family.model_config(
        cell.config, rehearsal, max_seq_len=int(job["seq_len"]),
        **launch.get("model", {}),
    )
    config = TrainingConfig(
        tensor_parallel_size=tp, pipeline_parallel_size=pp,
        sequence_parallel=bool(launch.get("sequence_parallel", False)),
        # under a pipeline the pipelined model does its own micro-batching
        num_microbatches=1 if pp > 1 else int(job["microbatches"]),
        pipeline_schedule=layout.get("schedule") if pp > 1 else None,
        num_model_chunks=1 if pp > 1 else None,
    )
    config.initialize(devices=list(devices))
    model = family.train_model(model_cfg)
    if pp > 1:
        model = PipelinedCausalLM(
            model, num_microbatches=int(job["microbatches"]),
            schedule=layout["schedule"],
        )
    return model, config, make_train_step(model, config), model_cfg


def run(cell, family, seed: int, seconds: float, rehearsal: bool, trace: bool,
        split: Dict[str, float], t_process: float) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_llama3_2_tpu.trainer import initialize_parallel_model

    job = cell.traffic
    devices = jax.devices()[: cell.chips]
    t0 = time.perf_counter()
    model, config, step, model_cfg = build_trainer(cell, family, devices, rehearsal)
    state, _ = initialize_parallel_model(model, config, key=jax.random.key(seed))
    jax.block_until_ready(state)
    split["init_s"] = time.perf_counter() - t0
    gbs, seq, vocab = int(job["global_batch"]), int(job["seq_len"]), model_cfg.vocab_size
    shapes = {
        jax.tree_util.keystr(p): tuple(x.shape)
        for p, x in jax.tree_util.tree_leaves_with_path(state.params)
    }

    # correctness, inside set-up: the first warm-up step trains on a batch
    # that tiles `check_sequences` distinct sequences, so its loss (a mean
    # over the global batch, however rows fall into micro-batches) is the loss
    # of those sequences — which the reference computes from the same
    # pre-step weights
    t0 = time.perf_counter()
    n_check = int(job["check_sequences"])
    distinct = traffic_mod.train_batch(n_check, seq, vocab, seed, step=WARMUP_STREAM)
    canonical = getattr(model, "from_pipeline", lambda p: p)
    ref_loss = check.reference_loss(family, model_cfg, state.params, canonical, jnp.asarray(distinct))
    split["check_s"] = time.perf_counter() - t0

    def batch_of(ids: np.ndarray):
        return {"input_ids": ids, "labels": ids}

    t0 = time.perf_counter()
    losses, grad_norms = [], []
    for i in range(int(job["warmup_steps"])):
        ids = (
            np.tile(distinct, (gbs // n_check, 1)) if i == 0
            else traffic_mod.train_batch(gbs, seq, vocab, seed, step=WARMUP_STREAM + i)
        )
        state, metrics = step(state, batch_of(ids))
        jax.block_until_ready(metrics["loss"])
        if i == 0:
            checked = check.compare_loss(float(metrics["loss"]), ref_loss, job.get("loss_tolerance"))
        losses.append(metrics["loss"])
        grad_norms.append(metrics["grad_norm"])
    split["warmup_s"] = time.perf_counter() - t0
    compiles_before = step._cache_size()
    setup_s = time.perf_counter() - t_process

    def one_step(index: int):
        """Input path, dispatch, wait — each under a host span of its own."""
        nonlocal state
        with profile.annotate("make_batch"):
            ids = traffic_mod.train_batch(gbs, seq, vocab, seed, step=index)
        with profile.annotate("train_step_dispatch"):
            state, metrics = step(state, batch_of(ids))
        with profile.annotate("wait_loss"):
            jax.block_until_ready(metrics["loss"])
        return metrics

    periods = []
    t_open = last = time.perf_counter()
    n = 0
    while last < t_open + seconds:
        metrics = one_step(n)
        now = time.perf_counter()
        periods.append(now - last)
        last = now
        n += 1
        losses.append(metrics["loss"])
        grad_norms.append(metrics["grad_norm"])
    window = (t_open, last)

    captured = None
    if trace:
        captured = profile.capture(
            lambda: [one_step(n + k) for k in range(int(job.get("trace_steps", 3)))]
        )

    losses = [float(x) for x in losses]
    grad_norms = [float(x) for x in grad_norms]
    n_warm = int(job["warmup_steps"])
    bad_steps = sum(
        1 for a, b in zip(losses[n_warm:], grad_norms[n_warm:])
        if not (np.isfinite(a) and np.isfinite(b))
    )
    problems = []
    if not checked["ok"]:
        problems.append(f"step-0 loss differs from the reference: {checked}")
    if not all(np.isfinite(losses[:n_warm])):
        problems.append(f"non-finite warm-up loss {losses[:n_warm]}")
    if step._cache_size() != compiles_before:
        problems.append("the train step compiled again inside the window")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {
        "kind": "training",
        "cell": cell, "model_cfg": model_cfg, "layout": job["layout"],
        "param_shapes": shapes, "periods": periods, "window": window,
        "seconds": seconds, "tokens_per_step": gbs * seq, "chips": cell.chips,
        "losses": losses, "grad_norms": grad_norms, "check": checked,
        "attempted": n, "failed": bad_steps, "failures": [], "problems": problems,
        "correct": n > 0 and bad_steps == 0 and not problems,
        "setup_s": setup_s, "split": split,
        "memory_peak_bytes": int(max(peaks)),
        "profile": captured,
    }


def tokens_per_s_per_chip(result: Dict[str, Any]) -> float:
    """Tokens per step over the median step period over the chips. A period
    runs from one step's loss being ready to the next one's, so it holds the
    input path, the dispatch and the device's work."""
    return result["tokens_per_step"] / stats.median(result["periods"]) / result["chips"]
