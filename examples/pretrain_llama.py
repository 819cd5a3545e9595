"""Launchable Llama pretraining with checkpoint/resume.

TPU-native equivalent of the reference's canonical pretrain entrypoints
(``examples/training/llama/tp_zero1_llama_hf_pretrain/tp_zero1_llama_hf_pretrain.py:277-350``
train loop; ``tp_pp_llama_hf_pretrain/run_llama_nxd.py:204-239`` resume via
``load_checkpoint(tag="latest_if_exists")``). One process drives the whole
mesh — no torchrun/xmp.spawn.

Usage (tiny smoke run on the 8-device CPU mesh):

    python examples/pretrain_llama.py --model tiny --cpu-devices 8 \
        --tp 2 --global-batch 8 --seq-len 64 --steps 10 --synthetic 200000 \
        --ckpt-dir /tmp/ckpt --save-every 5

Re-running the same command resumes from the latest checkpoint.

Pipelined runs save parameters in canonical (L, ...) layer layout
(``from_pipeline`` before save, ``to_pipeline`` after load) so a checkpoint
written at pp=2 resumes at pp=4 or pp=1 (elastic pp resharding — the advisor
gap on shape-locked pipelined saves).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="tiny", help="LLAMA_CONFIGS key")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--sp", action="store_true", help="sequence parallelism")
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument(
        "--pp-schedule", default="gpipe",
        choices=["gpipe", "1f1b", "interleaved"],
        help="pipeline executor (docs/interleaved_vpp.md for tradeoffs)",
    )
    p.add_argument(
        "--model-chunks", type=int, default=1,
        help="interleaved VPP chunks per pp lane (--pp-schedule interleaved)",
    )
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=10)
    p.add_argument("--data", help="path to a .npy token stream")
    p.add_argument(
        "--synthetic", type=int, default=0,
        help="generate a synthetic token stream of this many tokens",
    )
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--save-every", type=int, default=50)
    p.add_argument("--async-save", action="store_true")
    p.add_argument("--keep-ckpts", type=int, default=3)
    p.add_argument("--metrics-file", default=None)
    p.add_argument(
        "--eval-every", type=int, default=0,
        help="run a held-out eval every N steps (0 = off)",
    )
    p.add_argument("--eval-batches", type=int, default=4)
    p.add_argument(
        "--tensorboard-dir", default=None,
        help="write TensorBoard scalar events (loss/grad_norm/lr/seq_s)",
    )
    p.add_argument(
        "--native-loader", action="store_true",
        help="use the C++ mmap+prefetch token loader (native/token_loader.cc)",
    )
    p.add_argument(
        "--timeline", default=None,
        help="write a Chrome-trace host timeline (events: step/data/ckpt)",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="capture an XLA device trace of steps 2-4 into this dir",
    )
    p.add_argument(
        "--capacity-factor", type=float, default=None,
        help="MoE capacity factor (required for --ep > 1 on MoE models)",
    )
    p.add_argument("--seed", type=int, default=42)
    p.add_argument(
        "--cpu-devices", type=int, default=0,
        help="force an n-device virtual CPU mesh (testing)",
    )
    return p.parse_args()


def main():
    args = parse_args()
    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache,
        set_cpu_devices,
    )

    if args.cpu_devices:
        set_cpu_devices(args.cpu_devices)
    enable_compile_cache()

    import numpy as np

    from neuronx_distributed_llama3_2_tpu.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )
    from neuronx_distributed_llama3_2_tpu.data import (
        DistributedDataLoader,
        LoaderState,
        TokenDataset,
        batch_to_device,
        write_token_file,
    )
    from neuronx_distributed_llama3_2_tpu.models import resolve_model
    from neuronx_distributed_llama3_2_tpu.pipeline import PipelinedCausalLM
    from neuronx_distributed_llama3_2_tpu.trainer import (
        OptimizerConfig,
        TrainState,
        TrainingConfig,
        initialize_parallel_model,
        make_eval_step,
        make_train_step,
    )
    from neuronx_distributed_llama3_2_tpu.trainer.metrics import (
        Throughput,
        TrainingMetrics,
    )
    from neuronx_distributed_llama3_2_tpu.trainer.optimizer import (
        OptimizerState,
        optimizer_state_specs,
    )
    from neuronx_distributed_llama3_2_tpu.utils.logger import get_logger

    logger = get_logger()

    def mask_mlm(tokens, rng, vocab_size):
        """15% MLM masking: labels carry the true token at masked spots,
        -100 elsewhere; [MASK] surrogate = vocab_size - 1 (synthetic
        streams have no reserved mask id). One recipe for train AND eval."""
        masked = np.array(tokens)
        labels = np.full_like(masked, -100)
        pick = rng.random(masked.shape) < 0.15
        labels[pick] = masked[pick]
        masked[pick] = vocab_size - 1
        return masked, labels

    # any family's *_CONFIGS key works (llama / mixtral / dbrx / gpt-neox /
    # codegen / bert — the reference ships one pretrain script per family;
    # here one script serves the whole registry)
    entry = resolve_model(args.model)
    model_cfg = entry["config"]
    if type(model_cfg).__name__ == "MllamaConfig":
        raise SystemExit(
            f"{args.model}: the vision family needs image inputs; this "
            f"text-pretraining CLI does not drive it. Use the library "
            f"(models/mllama.py + trainer) for vision fine-tunes."
        )
    is_bert = not hasattr(model_cfg, "max_seq_len")
    if is_bert:
        # BERT: fixed learned position table + MLM objective (masking below)
        if args.seq_len > model_cfg.max_position_embeddings:
            raise SystemExit(
                f"--seq-len {args.seq_len} exceeds {args.model}'s learned "
                f"position table ({model_cfg.max_position_embeddings})"
            )
    else:
        model_cfg = dataclasses.replace(
            model_cfg, max_seq_len=max(args.seq_len, model_cfg.max_seq_len)
        )
    if args.capacity_factor is not None:
        if not hasattr(model_cfg, "capacity_factor"):
            raise SystemExit(f"--capacity-factor: {args.model} is not a MoE model")
        model_cfg = dataclasses.replace(
            model_cfg, capacity_factor=args.capacity_factor
        )
    config = TrainingConfig(
        tensor_parallel_size=args.tp,
        pipeline_parallel_size=args.pp,
        # only pin the pipeline knobs when there IS a pipeline — on pp=1
        # the model is unpipelined and the knobs must stay None
        pipeline_schedule=args.pp_schedule if args.pp > 1 else None,
        num_model_chunks=args.model_chunks if args.pp > 1 else None,
        expert_parallel_size=args.ep,
        sequence_parallel=args.sp,
        # under pp the pipelined model does its own microbatching; the
        # trainer-level grad-accum loop must not split the batch again
        num_microbatches=1 if args.pp > 1 else args.microbatches,
        seed=args.seed,
        optimizer=OptimizerConfig(
            learning_rate=args.lr,
            warmup_steps=args.warmup_steps,
            total_steps=args.steps,
        ),
    )
    config.initialize()

    base_model = entry["model_cls"](model_cfg)
    pipelined = args.pp > 1
    model = (
        PipelinedCausalLM(
            base_model,
            num_microbatches=max(args.microbatches, args.pp),
            schedule=args.pp_schedule,
            num_model_chunks=args.model_chunks,
        )
        if pipelined
        else base_model
    )

    # -- data -------------------------------------------------------------
    data_path = args.data
    if args.synthetic:
        data_path = os.path.join(args.ckpt_dir, "synthetic_tokens.npy")
        if not os.path.exists(data_path):
            os.makedirs(args.ckpt_dir, exist_ok=True)
            rng = np.random.default_rng(args.seed)
            write_token_file(
                data_path,
                rng.integers(
                    0, model_cfg.vocab_size, args.synthetic, dtype=np.int32
                ),
            )
    if not data_path:
        raise SystemExit("pass --data FILE.npy or --synthetic N")
    dataset = None
    if args.native_loader:
        from neuronx_distributed_llama3_2_tpu.data.native_loader import (
            NativeTokenDataset,
            native_available,
        )

        if not native_available():
            raise SystemExit(
                "--native-loader: native/libtoken_loader.so could not be "
                "built (needs make and g++); drop the flag for the numpy "
                "loader"
            )
        dataset = NativeTokenDataset(data_path, args.seq_len)
    if dataset is None:
        dataset = TokenDataset(data_path, args.seq_len)
    # train/eval holdout: eval owns the TAIL of the sample space and its own
    # plain-numpy dataset handle — the native train dataset's one-slot
    # prefetch must never be shared (an eval gather would clobber the train
    # loop's outstanding prefetch and silently cross the data streams)
    n_samples = len(dataset)
    eval_loader = None
    train_range = None
    if args.eval_every:
        if args.eval_batches < 1:
            raise SystemExit("--eval-batches must be >= 1 when --eval-every is set")
        eval_n = max(args.global_batch * args.eval_batches, n_samples // 20)
        if n_samples - eval_n < args.global_batch:
            raise SystemExit(
                f"dataset too small to hold out {eval_n} eval samples"
            )
        train_range = (0, n_samples - eval_n)
        eval_loader = DistributedDataLoader(
            TokenDataset(data_path, args.seq_len),
            args.global_batch,
            shuffle=False,
            sample_range=(n_samples - eval_n, n_samples),
        )
    loader = DistributedDataLoader(
        dataset,
        args.global_batch,
        seed=args.seed,
        sample_range=train_range,
    )
    eval_step_fn = None  # built lazily, once (jit cache lives on the fn)

    # -- model/optimizer state (fresh, then maybe overwritten by resume) ---
    state, _ = initialize_parallel_model(model, config)
    step_fn = make_train_step(model, config)
    mesh = None  # default: live parallel state's mesh

    # canonical (L, ...) layout templates/specs for elastic-pp checkpoints
    def to_canonical(tree):
        return model.from_pipeline(tree) if pipelined else tree

    def from_canonical(tree):
        return model.to_pipeline(tree) if pipelined else tree

    def opt_map(opt: OptimizerState, fn) -> OptimizerState:
        return OptimizerState(
            step=opt.step,
            master=None if opt.master is None else fn(opt.master),
            mu=fn(opt.mu),
            nu=fn(opt.nu),
        )

    canonical_params_t = jax.eval_shape(to_canonical, state.params)
    canonical_specs = base_model.specs()
    canonical_opt_t = jax.eval_shape(
        lambda o: opt_map(o, to_canonical), state.opt
    )
    canonical_opt_specs = optimizer_state_specs(
        canonical_specs, canonical_params_t, config.optimizer
    )

    start_step = 0
    loaded = load_checkpoint(
        args.ckpt_dir,
        tag="latest_if_exists",
        model=canonical_params_t,
        optimizer=canonical_opt_t,
        model_specs=canonical_specs,
        optimizer_specs=canonical_opt_specs,
        mesh=mesh,
    )
    if loaded is not None:
        state = TrainState(
            params=from_canonical(loaded["model"]),
            opt=opt_map(loaded["optimizer"], from_canonical),
        )
        uc = loaded.get("user_content") or {}
        start_step = int(uc.get("step", 0))
        loader.state = LoaderState.from_json(uc.get("loader", {}))
        logger.info(
            "resumed from %s at step %d", loaded["tag"], start_step
        )

    # -- train loop (reference tp_zero1_llama_hf_pretrain.py:277-350) -----
    tb = None
    if args.tensorboard_dir:
        from neuronx_distributed_llama3_2_tpu.trainer import TensorBoardLogger

        tb = TensorBoardLogger(args.tensorboard_dir)
    metrics_file = (
        TrainingMetrics(args.metrics_file) if args.metrics_file else None
    )
    throughput = Throughput(args.global_batch)
    batches = iter(loader)

    def save(tag_step: int):
        save_checkpoint(
            args.ckpt_dir,
            tag=f"step_{tag_step}",
            model=to_canonical(state.params),
            optimizer=opt_map(state.opt, to_canonical),
            user_content={"step": tag_step, "loader": loader.state.to_json()},
            async_save=args.async_save,
            num_kept_ckpts=args.keep_ckpts,
        )

    from neuronx_distributed_llama3_2_tpu.utils.profiler import (
        Timeline,
        device_trace,
        step_annotation,
    )

    timeline = Timeline(args.timeline)
    profile_ctx = None

    def stop_profile():
        nonlocal profile_ctx
        if profile_ctx is not None:
            profile_ctx.__exit__(None, None, None)
            profile_ctx = None

    # always stop the trace, even when the run ends (or raises) inside the
    # profiling window — an unstopped trace is never flushed to disk
    import atexit

    atexit.register(stop_profile)
    for step in range(start_step, args.steps):
        if args.profile_dir and step == start_step + 2:
            profile_ctx = device_trace(args.profile_dir)
            profile_ctx.__enter__()
        with timeline.event("load_batch", cat="data"):
            batch = next(batches)
            if is_bert:
                # MLM objective (causal next-token labels would make BERT's
                # bidirectional encoder solve a trivial copy task)
                masked, labels = mask_mlm(
                    batch,
                    np.random.default_rng(args.seed * 100003 + step),
                    model_cfg.vocab_size,
                )
                ids = batch_to_device(masked, mesh)
                lbl = batch_to_device(labels, mesh)
            else:
                ids = batch_to_device(batch, mesh)
                lbl = ids
        t0 = time.perf_counter()
        with timeline.event("train_step", cat="step"), step_annotation(step):
            state, m = step_fn(state, {"input_ids": ids, "labels": lbl})
            loss = float(m["loss"])  # blocks until the step finished
        if args.profile_dir and step == start_step + 4:
            stop_profile()
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite loss {loss} at step {step}")
        seqs_per_s = throughput.tick()
        logger.info(
            "step %d loss %.4f grad_norm %.3f lr %.2e (%.0f ms)%s",
            step, loss, float(m["grad_norm"]), float(m["learning_rate"]),
            (time.perf_counter() - t0) * 1e3,
            f" {seqs_per_s:.2f} seq/s" if seqs_per_s else "",
        )
        if metrics_file:
            metrics_file.log(
                step, loss=loss, grad_norm=float(m["grad_norm"]),
                lr=float(m["learning_rate"]),
                seqs_per_s=seqs_per_s,
            )
        if tb:
            tb.log_scalars(
                step,
                {
                    "train/loss": loss,
                    "train/grad_norm": float(m["grad_norm"]),
                    "train/lr": float(m["learning_rate"]),
                    **({"train/seqs_per_s": seqs_per_s} if seqs_per_s else {}),
                },
            )
        if eval_loader is not None and (step + 1) % args.eval_every == 0:
            from neuronx_distributed_llama3_2_tpu.trainer import evaluate

            if eval_step_fn is None:
                eval_step_fn = make_eval_step(model, config)

            def eval_batches():
                # stateless fixed slice (batch_at): identical samples every
                # interval, so successive eval losses are comparable
                for i in range(args.eval_batches):
                    ev = np.array(eval_loader.batch_at(i))
                    if is_bert:
                        # fixed-seed masking: same positions each eval
                        ev, lbl = mask_mlm(
                            ev,
                            np.random.default_rng(args.seed * 7919 + i),
                            model_cfg.vocab_size,
                        )
                    else:
                        lbl = ev
                    yield {
                        "input_ids": batch_to_device(ev, mesh),
                        "labels": batch_to_device(lbl, mesh),
                    }

            ev_loss = evaluate(
                model, config, state.params, eval_batches(),
                eval_step=eval_step_fn,
            )
            logger.info("step %d eval_loss %.4f", step, ev_loss)
            if tb:
                tb.log_scalars(step, {"eval/loss": ev_loss})
            if metrics_file:
                metrics_file.log(step, eval_loss=ev_loss)
            throughput.reset()  # eval wall time must not read as a dip
        if (
            args.save_every > 0
            and (step + 1) % args.save_every == 0
            and step + 1 < args.steps
        ):
            with timeline.event("save_checkpoint", cat="ckpt", step=step + 1):
                save(step + 1)
            throughput.reset()  # blocking save time isn't training time
        timeline.step_end(step)
    # skip on a no-op resume: rewriting the completed final checkpoint would
    # unmark done and risk losing it if killed mid-write
    if start_step < args.steps:
        save(args.steps)
    timeline.close()
    if tb:
        tb.close()
    from neuronx_distributed_llama3_2_tpu.checkpoint import (
        finalize_async_saves,
    )

    finalize_async_saves()
    logger.info("done: %d steps", args.steps)


if __name__ == "__main__":
    main()
