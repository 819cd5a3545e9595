"""Serving cells: the program's ``InferenceEngine`` -> ``PagedServingEngine``
-> ``GraftServer`` on 127.0.0.1, loaded over HTTP/SSE by a generator in a
thread and event loop of its own (one process; why: ``drive``).

The benchmark passes *sizes* (lanes, pool blocks, bucket ladders, chunk size,
``prewarm=True``) and never a hot-path choice: ``use_paged_kernel``,
``async_loop``, ``fused_step``, ``on_device_sampling``, ``kv_cache_dtype``,
``step_policy``, ``spec_*`` and ``spill_enabled`` stay as the program ships
them, so a PR that makes a better path the default shows as a gain.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import json
import threading
import time
from typing import Any, Dict, List, Optional

from benchmarks import check, profile, traffic as traffic_mod


@dataclasses.dataclass
class Sample:
    """One request as its client saw it; times are ``time.perf_counter()``."""

    index: int
    prompt_tokens: int
    prefix_id: Optional[int]
    due: float
    sent: Optional[float] = None
    first_token: Optional[float] = None
    last_token: Optional[float] = None
    done: Optional[float] = None
    tokens: int = 0
    cached_tokens: int = 0
    queue_ms: Optional[float] = None
    error: Optional[str] = None
    measured: bool = True      # False: lead-in or traced tail


# ---------------------------------------------------------------------------
# set-up: weights, engine, pool and prewarm, correctness check
# ---------------------------------------------------------------------------

def build(cell, family, seed: int, rehearsal: bool, trace: bool, split: Dict[str, float],
          calibrate: Optional[Dict[str, Any]] = None):
    """(serving engine, model config, check result). ``split`` receives the
    seconds of each set-up phase. ``calibrate`` is for ``tools/check_calibrate.py``
    alone: ``PagedConfig`` fields of a variant the check has to fail (an 8-bit
    pool); a cell never passes one."""
    import jax

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig, InferenceEngine, SamplingConfig,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig, PagedServingEngine,
    )

    sizes = cell.traffic["engine"]
    t0 = time.perf_counter()
    model_cfg = family.model_config(
        cell.config, rehearsal, max_seq_len=sizes["max_seq_len"]
    )
    train_model = family.train_model(model_cfg)
    # every weight on the device in one jitted call from the seed, in the
    # dtype it is served in
    params = jax.block_until_ready(jax.jit(train_model.init)(jax.random.key(seed)))
    engine = InferenceEngine(
        model_cfg, params, max_batch=sizes["lanes"], max_seq_len=sizes["max_seq_len"],
    )
    del params
    split["init_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    serving = PagedServingEngine(
        engine,
        GenerationConfig(
            max_new_tokens=int(cell.traffic["output_tokens"]),
            sampling=SamplingConfig(greedy=True), seed=seed,
        ),
        PagedConfig(
            # the pool is a size of the cell, fixed in blocks: one taken from
            # free memory would grow, and its per-step copy with it, when a
            # PR frees memory
            block_size=sizes["block_size"], num_blocks=int(sizes["pool_blocks"]),
            prewarm=True,
            prefill_chunk_tokens=sizes["prefill_chunk_tokens"],
            prefill_buckets=tuple(sizes["prefill_buckets"]),
            kv_buckets=tuple(sizes["kv_buckets"]),
            # observability, not a hot-path choice, and the traced run only:
            # the dispatch records label the device's anonymous `jit_fn` runs
            trace_enabled=bool(trace), trace_buffer_steps=1 << 16,
            **(calibrate or {}),
        ),
    )
    split["prewarm_s"] = time.perf_counter() - t0

    # the correctness check runs on the engine the window measures: its
    # programs and pool emit the tokens, its model and its kind of pool the
    # logits
    t0 = time.perf_counter()
    checked = check.serving_engine(
        serving, family, model_cfg, cell.traffic["check"], sizes, seed=seed,
        service_class=cell.traffic.get("service_class", "batch"),
    )
    split["check_s"] = time.perf_counter() - t0
    return serving, model_cfg, checked


# ---------------------------------------------------------------------------
# the HTTP/SSE client
# ---------------------------------------------------------------------------

async def _send(host: str, port: int, req: traffic_mod.Request, sample: Sample,
                vocab: int, service_class: str = "batch") -> None:
    """POST one streamed completion and stamp the sample as events arrive."""
    try:
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({
            "prompt": req.prompt, "stream": True, "service_class": service_class,
        }).encode()
        sample.sent = time.perf_counter()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n".encode() + body
        )
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split()[1])
        if status != 200:
            sample.error = f"HTTP {status}: {(await reader.read())[:200]!r}"
            return
        streamed: List[int] = []
        final = None
        while True:
            event = (await reader.readuntil(b"\n\n")).decode().strip()
            now = time.perf_counter()
            if event == "data: [DONE]":
                break
            payload = json.loads(event[len("data: "):])
            if "token" in payload:
                if sample.first_token is None:
                    sample.first_token = now
                sample.last_token = now
                streamed.append(payload["token"])
            else:
                final = payload
        sample.done = time.perf_counter()
        sample.tokens = len(streamed)
        if final is None:
            sample.error = "stream ended without a final payload"
        elif final["status"] != "finished" or final["error"] is not None:
            sample.error = f"{final['status']}: {final['error']}"
        elif final["choices"][0]["token_ids"] != streamed:
            sample.error = "streamed tokens differ from the final payload"
        elif not all(isinstance(t, int) and 0 <= t < vocab for t in streamed):
            sample.error = "a streamed token lies outside the vocabulary"
        if final is not None:
            sample.cached_tokens = int(final["usage"]["cached_tokens"])
            sample.queue_ms = final["timing"]["queue_ms"]
        writer.close()
    except asyncio.CancelledError:
        raise
    except Exception as e:  # a refused or broken request is a failed request
        sample.error = f"{type(e).__name__}: {e}"


# ---------------------------------------------------------------------------
# the measured run
# ---------------------------------------------------------------------------

async def drive(cell, serving, vocab: int, seed: int, seconds: float, trace: bool):
    """Serve the cell's traffic for lead-in + window (+ the traced segment) and
    return (samples, window, snapshots).

    Two threads, one process. This (main) thread runs the event loop in which
    ``GraftServer`` steps the engine; the load generator and its HTTP clients
    run in an event loop of their own in a second thread, because the server
    yields to its loop once per engine step: clients in the same loop were
    late by a whole step per socket event (``gen_late_p90_ms`` 400-450 ms at
    every rate, my chip runs of PR 22), which a user on another host is not."""
    from neuronx_distributed_llama3_2_tpu.serving import GraftServer

    tr = cell.traffic
    lead = float(tr.get("lead_s", 0.0))
    # the traced segment follows the measured window under the same traffic,
    # so starting and stopping the profiler disturbs no counted sample
    tail = float(tr.get("trace_s", 3.0)) + 1.0 if trace else 0.0
    horizon = lead + seconds + tail
    if trace:
        # a host span around the call the server makes, so that idle gaps on
        # the device can be told apart: inside a step, or between steps
        step = serving.step

        def annotated_step():
            with profile.annotate("engine.step"):
                return step()

        serving.step = annotated_step
    server = GraftServer(serving)
    host, port = await server.serve_http()
    samples: List[Sample] = []
    t_start = time.perf_counter() + 0.2      # the generator's thread starts up first
    window = (t_start + lead, t_start + lead + seconds)
    snapshots: Dict[str, Any] = {}
    service_class = tr.get("service_class", "batch")

    async def generate():
        tasks: List[asyncio.Task] = []

        def launch(req: traffic_mod.Request, due: float, measured: bool) -> asyncio.Task:
            s = Sample(len(samples), len(req.prompt), req.prefix_id, due, measured=measured)
            samples.append(s)
            tasks.append(asyncio.ensure_future(_send(host, port, req, s, vocab, service_class)))
            return tasks[-1]

        if tr["kind"] == "open_poisson":
            for req in traffic_mod.open_loop(tr, seconds, vocab, seed, tail):
                due = t_start + req.due_s
                with profile.annotate("generator_wait"):
                    delay = due - time.perf_counter()
                    if delay > 0:
                        await asyncio.sleep(delay)
                launch(req, due, req.measured)
        else:
            pool = traffic_mod.closed_loop(tr, vocab, seed)
            cursor = itertools.count()

            async def client():
                while (now := time.perf_counter()) < t_start + horizon:
                    await launch(pool[next(cursor) % len(pool)], now, True)

            await asyncio.sleep(max(t_start - time.perf_counter(), 0))
            await asyncio.gather(*(client() for _ in range(int(tr["clients"]))))
        # drain: nothing new is sent; what was sent may finish, within a limit
        pending = [t for t in tasks if not t.done()]
        if pending:
            await asyncio.wait(pending, timeout=float(tr["drain_s"]))
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    generator = threading.Thread(target=lambda: asyncio.run(generate()), name="load-generator")
    generator.start()
    # counter snapshots at the window's edges; the profiler after it
    await asyncio.sleep(max(window[0] - time.perf_counter(), 0))
    snapshots["open"] = serving.metrics.snapshot()
    await asyncio.sleep(max(window[1] - time.perf_counter(), 0))
    snapshots["close"] = serving.metrics.snapshot()
    if trace:
        snapshots["trace"] = await profile.capture_async(tail - 1.0, steps_of=serving.tracer)
    await asyncio.get_running_loop().run_in_executor(None, generator.join)
    for rid, req in list(serving._requests.items()):
        if not req.done:
            server.cancel(rid, reason="benchmark window closed")
    await asyncio.sleep(0.05)
    await server.close()
    return samples, window, snapshots


def window_failures(in_window: List[Sample], want: int) -> List[str]:
    """What failed among the requests due in the window. One that is still
    unfinished at the drain limit is a failed request: dropped in silence, the
    slowest requests of a cell pushed past its knee would leave the medians
    and ``correct`` untouched."""
    failures = []
    for s in in_window:
        if s.error is not None:
            failures.append(f"request {s.index}: {s.error}")
        elif s.done is None:
            failures.append(f"request {s.index}: unfinished at the drain limit")
        elif s.tokens != want:
            failures.append(f"request {s.index}: {s.tokens} tokens, asked for {want}")
    return failures


def run(cell, family, seed: int, seconds: float, rehearsal: bool, trace: bool,
        split: Dict[str, float], t_process: float) -> Dict[str, Any]:
    """Build, check, run, and return everything metric readers may want."""
    import jax

    from neuronx_distributed_llama3_2_tpu.serving import audit_engine

    serving, model_cfg, checked = build(cell, family, seed, rehearsal, trace, split)
    setup_s = time.perf_counter() - t_process
    samples, window, snaps = asyncio.run(
        drive(cell, serving, model_cfg.vocab_size, seed, seconds, trace)
    )
    want = int(cell.traffic["output_tokens"])
    # open loop: the requests of the window's own segment (a fixed set);
    # closed loop: whatever the clients sent inside the window
    in_window = [s for s in samples if s.measured and window[0] <= s.due < window[1]]
    failures = window_failures(in_window, want)
    unfinished = sum(1 for s in in_window if s.done is None and s.error is None)
    problems = []
    m = serving.metrics
    if m.steadystate_compiles:
        problems.append(f"{m.steadystate_compiles} compiles inside the window")
    leaked = serving.allocator.leak_check()
    audit = audit_engine(serving)
    if leaked or audit:
        problems.append(f"leaked blocks {leaked[:8]}, audit {audit[:4]}")
    if not checked["ok"]:
        problems.append(f"logits differ from the reference: {checked}")
    stats_dev = jax.devices()[0].memory_stats() or {}
    return {
        "kind": "serving",
        "cell": cell, "model_cfg": model_cfg, "serving": serving,
        "samples": samples, "in_window": in_window, "window": window,
        "seconds": seconds, "snapshots": snaps, "check": checked,
        "attempted": len(in_window), "failed": len(failures),
        "unfinished": unfinished, "failures": failures, "problems": problems,
        "correct": not failures and not problems and bool(in_window),
        "setup_s": setup_s, "split": split,
        "memory_peak_bytes": int(stats_dev.get("peak_bytes_in_use", 0)),
        "profile": snaps.get("trace"),
    }


# ---------------------------------------------------------------------------
# end-to-end metrics (host clock, client side)
# ---------------------------------------------------------------------------

def ttft_ms(samples: List[Sample]) -> List[float]:
    """From the instant a request was *due* to its first SSE token."""
    return [(s.first_token - s.due) * 1e3 for s in samples if s.first_token is not None]


def tpot_ms(samples: List[Sample]) -> List[float]:
    return [
        (s.last_token - s.first_token) * 1e3 / (s.tokens - 1)
        for s in samples if s.done is not None and s.error is None and s.tokens > 1
    ]


def tokens_per_s(samples: List[Sample], window) -> float:
    """Prompt + output tokens of completed requests, each credited with the
    share of its lifetime (sent -> done) that fell inside the window, over the
    window's length. A request wholly inside counts whole; crediting the ones
    that straddle an edge by their overlap keeps the count from jumping by a
    whole document when an edge moves by a millisecond."""
    total = 0.0
    for s in samples:
        if s.done is None or s.error is not None or s.sent is None:
            continue
        life = max(s.done - s.sent, 1e-9)
        overlap = max(0.0, min(s.done, window[1]) - max(s.sent, window[0]))
        total += (s.prompt_tokens + s.tokens) * overlap / life
    return total / (window[1] - window[0])
