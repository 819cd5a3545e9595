"""Chip smoke: the main path, once, on the chip — trainer and paged server.

``python chip_smoke.py`` drives Llama-3.2-1B at full width and depth
(16 layers, hidden 2048, 32/8 heads of 64, vocab 128,256, bf16, random
weights from a seed) through the entry points a user calls:

- *train*: ``TrainingConfig.initialize`` → ``initialize_parallel_model`` →
  ``make_train_step`` at the bench's settings (``bench.bench_setup``), a
  fixed batch repeated; every loss and grad-norm finite, the last loss
  below the first, and the traced attention is the Pallas flash kernel
  compiled by Mosaic.
- *serve* (gather path, then ``use_paged_kernel``): ``InferenceEngine`` →
  ``PagedServingEngine`` (prewarmed, chunked prefill, pool sized from HBM) →
  ``GraftServer.serve_http`` on localhost; eight ``POST /v1/completions``,
  one streamed over SSE, two sharing a 512-token prefix. Every request
  answers with the token count asked for, the radix cache hits, nothing
  compiles after prewarm, nothing leaks; then one decode step of the kernel
  model against the gather model on the same pool, compared on logits.
- on four chips also: train at tp=2 × dp=2 with the default (ZeRO-1, fp32
  master) optimizer, train at pp=2 × tp=2 on the 1F1B executor, serve on a
  pure tp=4 mesh with the kernel on; parameters, optimizer state and the KV
  pool must be spread over all four devices.

One process runs the legs in turn and frees each leg's state, because a
chip belongs to one process. No TPU → non-zero exit before any work and no
result line. Each leg prints its result as it ends (``leg <name>: {...}``),
the line before last holds them all (``legs: {...}``), and the last stdout
line is one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it.

``--rehearse-on-cpu`` runs the same code at a tiny size on virtual CPU
devices with the Pallas kernels interpreted, and says so; it is the check to
run before spending chip time, never the default and never a device result.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
import traceback

# logits of the kernel path against the gather path after 16 layers of bf16
# activations (eps 2^-8 a rounding, ~sqrt(16) roundings deep): max |Δ| over
# max |logit|. Not compared on sampled tokens — random-init logits are
# near-ties and an argmax flip is not a fault.
LOGIT_TOLERANCE = 5e-2
# first-step loss and grad norm of a train leg against the first train leg of
# the run: same seed, same batch, same model, another mesh — only the order of
# bf16 roundings differs
MESH_AGREEMENT = 1e-2
# per-device bytes_in_use, largest over smallest, on the four-chip legs
# (pipeline stages differ by the embedding and the head)
MEMORY_BALANCE = 2.0


TRAIN_STEPS = 5


@dataclasses.dataclass(frozen=True)
class ServeSizes:
    """The serve legs' shapes: the chip run's, and the CPU rehearsal's cut.
    (The train legs take theirs from ``bench.bench_setup``; the rehearsal's
    training cut is spelled out in :func:`train_leg`.)"""

    model: str
    max_seq_len: int
    prompts: tuple         # (prefix tokens shared with request 0, own tokens)
    max_new_tokens: int
    prefill_chunk: int
    prefill_buckets: tuple
    kv_buckets: tuple
    compare_prompt: int    # prompt rows behind the kernel-vs-gather step


CHIP = ServeSizes(
    model="llama3.2-1b", max_seq_len=2048,
    # request 0 and 1 share a 512-token prefix; lengths 64..1,024
    prompts=((0, 576), (512, 64), (0, 64), (0, 128), (0, 256), (0, 384),
             (0, 768), (0, 1024)),
    max_new_tokens=32, prefill_chunk=256, prefill_buckets=(256, 1024),
    kv_buckets=(1024,), compare_prompt=384,
)
REHEARSAL = ServeSizes(
    model="tiny", max_seq_len=128,
    prompts=((0, 40), (32, 8), (0, 8), (0, 16), (0, 24), (0, 32), (0, 48),
             (0, 64)),
    max_new_tokens=8, prefill_chunk=16, prefill_buckets=(16, 64),
    kv_buckets=(64,), compare_prompt=24,
)

ONE_CHIP_LEGS = ("train", "serve_gather", "serve_kernel")
FOUR_CHIP_LEGS = ("train_tp2dp2", "train_pp2tp2", "serve_tp4")


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def free_leg_state():
    """Drop the mesh and every cached executable so the next leg starts with
    the chip's memory back (the caller has already dropped its arrays)."""
    import jax

    from neuronx_distributed_llama3_2_tpu.parallel import state as ps

    ps.destroy_model_parallel()
    gc.collect()
    jax.clear_caches()


def spread_check(name, tree, devices):
    """Every array of ``tree`` lives on all of ``devices``."""
    import jax

    want = set(devices)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        got = set(leaf.sharding.device_set)
        if got != want:
            raise AssertionError(
                f"{name}{jax.tree_util.keystr(path)} lives on "
                f"{len(got)} of {len(want)} devices"
            )


def balance_check(devices):
    """Per-device ``bytes_in_use`` within :data:`MEMORY_BALANCE` of each
    other (the CPU rehearsal's backend reports no memory stats)."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return {"bytes_in_use": "not measured (no memory stats on this backend)"}
    used = [int(s["bytes_in_use"]) for s in stats]
    ratio = max(used) / max(min(used), 1)
    if ratio > MEMORY_BALANCE:
        raise AssertionError(
            f"per-device bytes_in_use {used}: largest/smallest {ratio:.2f} "
            f"> {MEMORY_BALANCE}"
        )
    return {"bytes_in_use": used, "largest_over_smallest": round(ratio, 3)}


# ---------------------------------------------------------------------------
# train legs
# ---------------------------------------------------------------------------

def train_leg(rehearsal, devices, tp=1, pp=1, default_optimizer=False):
    """A few optimizer steps on one fixed batch through the trainer's own
    entry points; see the module docstring for what is asserted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import bench_setup
    from neuronx_distributed_llama3_2_tpu.kernels.mode import kernel_mode
    from neuronx_distributed_llama3_2_tpu.models import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.pipeline import PipelinedCausalLM
    from neuronx_distributed_llama3_2_tpu.trainer import (
        OptimizerConfig,
        initialize_parallel_model,
        make_train_step,
    )

    model_cfg, config, batch, seq = bench_setup()
    if rehearsal:
        batch, seq = 4, 128
        model_cfg = dataclasses.replace(
            LLAMA_CONFIGS["tiny"], max_seq_len=seq, use_flash_attention=True,
            flash_block_q=64, flash_block_kv=64, loss_chunk_size=32,
            remat="full",
        )
    optimizer = config.optimizer
    if default_optimizer:
        # ZeRO-1 + fp32 master weights and moments, 16 B/parameter: what
        # several chips are for. Only the warm-up is shortened so that five
        # steps move the loss.
        optimizer = OptimizerConfig(warmup_steps=1)
    config = dataclasses.replace(
        config, tensor_parallel_size=tp, pipeline_parallel_size=pp,
        optimizer=optimizer,
        pipeline_schedule="1f1b" if pp > 1 else None,
        num_model_chunks=1 if pp > 1 else None,
    )
    state_info = config.initialize(devices=devices)
    model = LlamaForCausalLM(model_cfg)
    if pp > 1:
        model = PipelinedCausalLM(model, num_microbatches=4, schedule="1f1b")
    state, _ = initialize_parallel_model(model, config)
    step = make_train_step(model, config)

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, model_cfg.vocab_size, (batch, seq)),
        dtype=jnp.int32,
    )
    data = {"input_ids": ids, "labels": ids}

    # which attention was traced: the Pallas flash kernel, and how it runs
    t0 = time.perf_counter()
    traced = step.trace(state, data)
    jaxpr_text = str(traced.jaxpr)
    if "flash_fwd" not in jaxpr_text or "flash_bwd_dkv" not in jaxpr_text:
        raise AssertionError("the traced step holds no Pallas flash kernel")
    lowered = traced.lower()
    mosaic = "tpu_custom_call" in lowered.as_text()
    mode = kernel_mode()
    if rehearsal:
        if mosaic or mode != "interpret":
            raise AssertionError(f"rehearsal expected interpreted kernels, got {mode}")
    elif not mosaic or mode != "compiled":
        raise AssertionError(
            f"flash attention is not a compiled Mosaic kernel (mode {mode})"
        )
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    losses, grad_norms, step_s = [], [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        state, metrics = compiled(state, data)
        jax.block_until_ready(metrics["loss"])
        step_s.append(time.perf_counter() - t0)
        # float() after block_until_ready: what the old bench synced on.
        # Its extra wait is reported, so the record shows the two agree.
        t0 = time.perf_counter()
        losses.append(float(metrics["loss"]))
        readback_s = time.perf_counter() - t0
        grad_norms.append(float(metrics["grad_norm"]))
    if not all(np.isfinite(losses)) or not all(np.isfinite(grad_norms)):
        raise AssertionError(f"non-finite: losses {losses} grad norms {grad_norms}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")

    result = {
        "mesh": {k: v for k, v in state_info.mesh.shape.items() if v > 1},
        "attention": "pallas flash, " + mode,
        "steps": len(losses),
        "losses": [round(x, 4) for x in losses],
        "grad_norms": [round(x, 4) for x in grad_norms],
        "compile_s": round(compile_s, 1),
        "step_s": [round(x, 3) for x in step_s],
        "readback_after_block_ms": round(readback_s * 1e3, 3),
    }
    if len(devices) > 1:
        spread_check("params", state.params, devices)
        spread_check("optimizer", state.opt, devices)
        result["spread"] = balance_check(devices)
    del state, compiled, lowered, traced, step, metrics
    return result


# ---------------------------------------------------------------------------
# serve legs
# ---------------------------------------------------------------------------

async def _http(host, port, method, path, payload=None):
    reader, writer = await asyncio.open_connection(host, port)
    body = json.dumps(payload).encode() if payload is not None else b""
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
        f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n".encode()
        + body
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, data = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), data


def _prompts(sizes, vocab):
    """Token-id prompts from a seed; entry ``(shared, own)`` starts with the
    first ``shared`` tokens of request 0."""
    import numpy as np

    rng = np.random.default_rng(7)
    out = []
    for shared, own in sizes.prompts:
        fresh = rng.integers(1, vocab, own).tolist()
        out.append(out[0][:shared] + fresh if shared else fresh)
    return out


async def _traffic(server, sizes, prompts):
    """Request 0 alone (its prefix must be in the radix index before its
    sibling arrives), then the other seven at once, the last over SSE."""
    host, port = await server.serve_http()
    try:
        async def complete(prompt, stream=False):
            status, data = await _http(
                host, port, "POST", "/v1/completions",
                {"prompt": prompt, "stream": stream},
            )
            if status != 200:
                raise AssertionError(f"POST /v1/completions -> {status}: {data[:200]}")
            if not stream:
                return json.loads(data)
            events = [
                json.loads(line[len("data: "):])
                for line in data.decode().split("\n\n")
                if line.startswith("data: ") and line != "data: [DONE]"
            ]
            tokens = [e["token"] for e in events if "token" in e]
            final = [e for e in events if "choices" in e][-1]
            if "data: [DONE]" not in data.decode():
                raise AssertionError("SSE stream did not end with [DONE]")
            if final["choices"][0]["token_ids"] != tokens:
                raise AssertionError("SSE token events differ from the final payload")
            return final

        t0 = time.perf_counter()
        responses = [await complete(prompts[0])]
        responses += await asyncio.gather(*(
            complete(p, stream=(i == len(prompts) - 2))
            for i, p in enumerate(prompts[1:])
        ))
        traffic_s = time.perf_counter() - t0
        for route in ("/metrics", "/snapshot"):
            status, data = await _http(host, port, "GET", route)
            if status != 200 or not data:
                raise AssertionError(f"GET {route} -> {status}")
        snapshot = json.loads(data)
    finally:
        await server.close()
    for prompt, resp in zip(prompts, responses):
        usage = resp["usage"]
        if resp["status"] != "finished" or resp["error"] is not None:
            raise AssertionError(f"request {resp['id']}: {resp['status']} {resp['error']}")
        if usage["completion_tokens"] != sizes.max_new_tokens or (
            len(resp["choices"][0]["token_ids"]) != sizes.max_new_tokens
        ):
            raise AssertionError(
                f"request {resp['id']}: {usage['completion_tokens']} tokens, "
                f"asked for {sizes.max_new_tokens}"
            )
        if usage["prompt_tokens"] != len(prompt):
            raise AssertionError(f"request {resp['id']}: prompt length mismatch")
    return responses, snapshot, traffic_s


def _kernel_vs_gather(model, params, sizes, lanes):
    """One decode step of the kernel model and of its ``use_paged_kernel=
    False`` twin over the same pool, tables and positions: max |Δ logit| over
    max |logit|. The pool rows come from a real paged prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    gather = type(model)(
        dataclasses.replace(model.config, use_paged_kernel=False)
    )
    bs, rows = 16, sizes.compare_prompt
    blocks = -(-rows // bs)
    kv_limit = sizes.kv_buckets[0]
    width = -(-kv_limit // bs)
    cache = model.init_paged_cache(1 + lanes * blocks, bs)
    tables = np.zeros((lanes, width), np.int32)
    for lane in range(lanes):
        tables[lane, :blocks] = 1 + lane * blocks + np.arange(blocks)
    tables = jnp.asarray(tables)
    ids = jnp.asarray(
        np.random.default_rng(11).integers(1, model.config.vocab_size, (lanes, rows)),
        jnp.int32,
    )
    _, cache = jax.jit(
        lambda p, c: gather.forward(
            p, c, ids[:, :-1], jnp.zeros((lanes,), jnp.int32), None,
            context_encode=True, return_hidden=True, block_tables=tables,
        )
    )(params, cache)
    tokens = ids[:, -1]
    positions = jnp.full((lanes,), rows - 1, jnp.int32)

    def step(m):
        out = jax.jit(
            lambda p, c: m.decode_step(
                p, c, tokens, positions, tables, kv_limit=kv_limit
            )[0]
        )(params, cache)
        return np.asarray(out, np.float32)

    ref, got = step(gather), step(model)
    if not np.isfinite(got).all():
        raise AssertionError("kernel-path logits are not finite")
    rel = float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-9))
    if rel > LOGIT_TOLERANCE:
        raise AssertionError(
            f"kernel vs gather logits differ by {rel:.3e} of max |logit| "
            f"(tolerance {LOGIT_TOLERANCE})"
        )
    return {
        "max_abs_diff_over_max_abs_logit": float(f"{rel:.3e}"),
        "tolerance": LOGIT_TOLERANCE,
        "argmax_agree": f"{int((got.argmax(-1) == ref.argmax(-1)).sum())}/{lanes}",
    }


def serve_leg(sizes, rehearsal, devices, kernel, tp=1):
    import jax
    from jax.sharding import NamedSharding

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
        SamplingConfig,
    )
    from neuronx_distributed_llama3_2_tpu.models import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.parallel import state as ps
    from neuronx_distributed_llama3_2_tpu.serving import (
        GraftServer,
        PagedConfig,
        PagedServingEngine,
        audit_engine,
    )
    from neuronx_distributed_llama3_2_tpu.serving.accounting import (
        device_hbm_budget,
    )

    cfg = dataclasses.replace(
        LLAMA_CONFIGS[sizes.model], max_seq_len=sizes.max_seq_len,
        use_paged_kernel=kernel,
    )
    train_model = LlamaForCausalLM(cfg)
    key = jax.random.key(0)
    if tp > 1:
        # InferenceEngine leaves everything on device 0 unless parallel state
        # is live, and takes the parameters as the caller placed them: the
        # weights are born sharded over the tp mesh
        st = ps.initialize_model_parallel(
            tensor_model_parallel_size=tp, devices=devices
        )
        shardings = jax.tree.map(
            lambda s: NamedSharding(st.mesh, s), train_model.specs()
        )
        params = jax.jit(train_model.init, out_shardings=shardings)(key)
    else:
        params = jax.jit(train_model.init)(key)
    lanes = 8
    engine = InferenceEngine(
        cfg, params, max_batch=lanes, max_seq_len=sizes.max_seq_len
    )

    # pool sized from what the device reports, not the default 128 blocks
    # (2,032 token rows): a third of what parameters and the dense engine's
    # cache leave, per chip — every paged program holds the donated pool
    # and a temporary of the same size (the layer scan reads the pool as xs
    # and writes it as ys), so two thirds is all a pool can ever take. The
    # kv-head-sharded pool holds tp× a chip's share.
    block_size = 16
    block_bytes = (
        2 * cfg.num_layers * block_size * cfg.num_kv_heads * cfg.head_dim
        * jax.numpy.dtype(cfg.dtype).itemsize
    )
    if rehearsal:
        num_blocks = 128
    else:
        held = sum(x.nbytes for x in jax.tree.leaves((params, engine.cache)))
        per_chip = (device_hbm_budget() - held // tp) // 3
        num_blocks = tp * per_chip // block_bytes
    t0 = time.perf_counter()
    serving = PagedServingEngine(
        engine,
        GenerationConfig(
            max_new_tokens=sizes.max_new_tokens,
            sampling=SamplingConfig(greedy=True),
        ),
        PagedConfig(
            block_size=block_size, num_blocks=int(num_blocks), prewarm=True,
            prefill_chunk_tokens=sizes.prefill_chunk,
            prefill_buckets=sizes.prefill_buckets,
            kv_buckets=sizes.kv_buckets,
        ),
    )
    prewarm_s = time.perf_counter() - t0
    path = serving.model.paged_dispatch_path(1)
    if path != ("kernel" if kernel else "gather"):
        raise AssertionError(f"decode dispatches through {path!r}")

    prompts = _prompts(sizes, cfg.vocab_size)
    responses, snapshot, traffic_s = asyncio.run(
        _traffic(GraftServer(serving), sizes, prompts)
    )
    m = serving.metrics
    if m.cached_tokens <= 0 or responses[1]["usage"]["cached_tokens"] <= 0:
        raise AssertionError("the shared prefix was not served from the radix cache")
    if m.steadystate_compiles != 0:
        raise AssertionError(f"{m.steadystate_compiles} compiles after prewarm")
    leaked = serving.allocator.leak_check()
    violations = audit_engine(serving)
    if leaked or violations:
        raise AssertionError(f"leaked blocks {leaked[:8]}, audit {violations[:4]}")

    result = {
        "mesh": {"tp": tp} if tp > 1 else {},
        "paged_dispatch_path": path,
        "requests": len(responses),
        "tokens_each": sizes.max_new_tokens,
        "prompt_tokens": [len(p) for p in prompts],
        "cached_tokens": m.cached_tokens,
        "programs_compiled": m.programs_compiled,
        "steadystate_compiles": m.steadystate_compiles,
        "pool_blocks": int(num_blocks),
        "pool_token_rows": int(num_blocks - 1) * block_size,
        "pool_bytes_per_chip": int(m.pool_bytes_per_rank),
        "prewarm_s": round(prewarm_s, 1),
        "traffic_s": round(traffic_s, 2),
        "snapshot_finished": snapshot["finished"],
    }
    if tp > 1:
        spread_check("params", params, devices)
        spread_check("pool", (serving.cache.k, serving.cache.v), devices)
        result["spread"] = balance_check(devices)
    pool = serving.cache
    del serving, responses
    gc.collect()
    for array in jax.tree.leaves(pool):
        array.delete()  # the leg's largest arrays: make room before the next
    if kernel and tp == 1:
        result["kernel_vs_gather"] = _kernel_vs_gather(
            engine.model, params, sizes, lanes
        )
    del engine, params
    return result


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_leg(name, sizes, rehearsal, devices):
    one, four = devices[:1], devices[:4]
    if name == "train":
        return train_leg(rehearsal, one)
    if name == "serve_gather":
        return serve_leg(sizes, rehearsal, one, kernel=False)
    if name == "serve_kernel":
        return serve_leg(sizes, rehearsal, one, kernel=True)
    if name == "train_tp2dp2":
        return train_leg(rehearsal, four, tp=2, default_optimizer=True)
    if name == "train_pp2tp2":
        return train_leg(rehearsal, four, tp=2, pp=2, default_optimizer=True)
    if name == "serve_tp4":
        return serve_leg(sizes, rehearsal, four, kernel=True, tp=4)
    raise SystemExit(f"unknown leg {name!r}")


def result_line(ok, device):
    """The last stdout line, to the chip check's contract: one JSON object
    with exactly ``ok`` and ``device`` (``platform``, ``kind``, ``count`` as
    JAX reports them). Per-leg results go on the lines before it."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", type=int, metavar="N", default=0,
        help="tiny-size rehearsal on N virtual CPU devices with interpreted "
             "kernels; not a device result",
    )
    ap.add_argument(
        "--legs", default=None,
        help="comma-separated subset of "
             f"{','.join(ONE_CHIP_LEGS + FOUR_CHIP_LEGS)} "
             "(default: every leg the device count allows)",
    )
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_on_cpu > 0

    import os

    if rehearsal:
        os.environ["NXDT_KERNEL_MODE"] = "interpret"
    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        device_summary,
        enable_compile_cache,
        require_tpu,
        set_cpu_devices,
    )

    if rehearsal:
        set_cpu_devices(args.rehearse_on_cpu)
        device = device_summary()
        print("REHEARSAL ON CPU — tiny sizes, interpreted kernels; "
              "nothing below is a device result")
    else:
        device = require_tpu()  # raises: no result line without a TPU
    import jaxlib

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # a CPU-only host can still rehearse
        libtpu = "not installed"
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    sizes = REHEARSAL if rehearsal else CHIP
    legs = (
        args.legs.split(",") if args.legs
        else ONE_CHIP_LEGS + (FOUR_CHIP_LEGS if device["count"] >= 4 else ())
    )
    devices = jax.devices()
    results, ok, first_train = {}, True, None
    for name in legs:
        t0 = time.perf_counter()
        try:
            results[name] = run_leg(name, sizes, rehearsal, devices)
            if name.startswith("train"):
                first_train = first_train or results[name]
                for key in ("losses", "grad_norms"):
                    got, want = results[name][key][0], first_train[key][0]
                    if abs(got - want) > MESH_AGREEMENT * abs(want):
                        raise AssertionError(
                            f"first-step {key} {got} on this mesh, {want} on "
                            f"{first_train['mesh'] or 'one chip'}"
                        )
            results[name]["ok"] = True
        except Exception as e:  # a failed leg fails the run, after the others
            traceback.print_exc()
            results[name] = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        finally:
            free_leg_state()
        results[name]["seconds"] = round(time.perf_counter() - t0, 1)
        print(f"leg {name}: {json.dumps(results[name])}", flush=True)
    print(f"legs: {json.dumps(results)}", flush=True)
    if rehearsal:
        print("REHEARSAL ON CPU — the line below names the host, not a chip")
    print(result_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
