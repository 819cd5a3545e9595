"""graftserve front door: asyncio server, HTTP transport, SLO scheduler.

Tier-1 gate for the streaming server (serving/server.py) and the
SLO-aware step policy (serving/scheduler.py), entirely on the tiny CPU
engine:

- concurrent asyncio clients stream token-identical outputs to the batch
  ``run_to_completion`` path (the stream is fed by the same readback);
- the hand-rolled HTTP transport round-trips completions (plain + SSE),
  request lookup, cancel, and both scrape endpoints;
- a prewarmed SloPolicy engine holds the zero-upload steady state and
  ``steadystate_compiles == 0`` — scheduling authority lives entirely in
  host-side action meta, so the device path must be byte-identical;
- the ``scripts/serving_load.py --smoke`` leg runs in-process, which is
  where the fifo-vs-slo acceptance comparison (interactive p99 TTFT
  improves, tokens/step within 5%) is enforced.

All runs finish with the invariant auditor, the block-pool leak check,
and the GC010 schedule automaton clean.
"""

import asyncio
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
    check_action_trace,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    GraftServer,
    PagedConfig,
    PagedServingEngine,
    audit_engine,
)
from neuronx_distributed_llama3_2_tpu.serving.policy import make_policy
from neuronx_distributed_llama3_2_tpu.serving.scheduler import SloPolicy

from tests.test_paged_serving import _prompts

TINY = LLAMA_CONFIGS["tiny"]

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts")


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _paged(params, gen, paged_cfg, **engine_kw):
    engine_kw.setdefault("max_batch", 4)
    engine_kw.setdefault("max_seq_len", 64)
    engine_kw.setdefault("buckets", [8, 16, 32])
    eng = InferenceEngine(TINY, params, **engine_kw)
    return PagedServingEngine(eng, gen, paged_cfg)


def _audit(eng):
    assert eng._pending is None
    assert eng.allocator.active_blocks == 0
    assert eng.allocator.leak_check() == []
    assert audit_engine(eng) == []
    assert check_action_trace(eng) == []


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_slo_policy_registered():
    """``step_policy="slo"`` resolves through the registry: scheduler.py
    is imported lazily by make_policy, so configs name it as a string."""
    pol = make_policy("slo")
    assert isinstance(pol, SloPolicy)
    assert pol.name == "slo"


def test_streamed_tokens_match_batch_run(params):
    """Concurrent streaming clients receive exactly the tokens the batch
    path commits, responses carry terminal timing + usage, and no stream
    is left open."""
    gen = GenerationConfig(max_new_tokens=6)
    cfg = dict(
        block_size=8, num_blocks=64, prefill_chunk_tokens=8, step_policy="slo",
    )
    prompts = _prompts(np.random.default_rng(7), (5, 12, 20, 9, 17))

    batch = _paged(params, gen, PagedConfig(**cfg))
    for p in prompts:
        batch.submit(p)
    expected = batch.run_to_completion()

    eng = _paged(params, gen, PagedConfig(**cfg))
    got = {}
    responses = {}

    async def client(srv, i, prompt):
        sc = "interactive" if i % 2 else "batch"
        rid = srv.submit(prompt, service_class=sc, tenant=f"t{i % 2}")
        toks = []
        async for t in srv.stream(rid):
            toks.append(t)
        got[rid] = toks
        responses[rid] = srv.response(rid)

    async def main():
        async with GraftServer(eng, idle_poll_s=0.002) as srv:
            await asyncio.gather(*(
                client(srv, i, p) for i, p in enumerate(prompts)
            ))
            return srv.snapshot()

    snap = asyncio.run(main())
    assert got == expected
    for rid, resp in responses.items():
        assert resp["status"] == "finished"
        assert resp["choices"][0]["token_ids"] == expected[rid]
        assert resp["choices"][0]["finish_reason"] in ("length", "stop")
        assert resp["error"] is None
        assert resp["usage"]["completion_tokens"] == len(expected[rid])
        assert resp["usage"]["prompt_tokens"] == len(prompts[rid])
        assert resp["timing"]["ttft_ms"] is not None
    assert snap["active_streams"] == 0
    assert snap["finished"] == len(prompts)
    assert snap["requests_by_class"]["interactive"]["finished"] == 2
    assert snap["requests_by_class"]["batch"]["finished"] == 3
    _audit(eng)


def test_cancel_mid_stream(params):
    """A client cancel mid-decode closes the stream, yields a structured
    ``cancelled`` error payload, and leaves the survivor token-identical
    to an uncancelled engine's output for the same rid."""
    gen = GenerationConfig(max_new_tokens=12)
    cfg = dict(block_size=8, num_blocks=64)
    prompts = _prompts(np.random.default_rng(9), (6, 10))

    solo = _paged(params, gen, PagedConfig(**cfg))
    for p in prompts:
        solo.submit(p)
    baseline = solo.run_to_completion()

    eng = _paged(params, gen, PagedConfig(**cfg))

    async def main():
        async with GraftServer(eng, idle_poll_s=0.002) as srv:
            victim = srv.submit(prompts[0])
            survivor = srv.submit(prompts[1])

            async def stream_victim():
                toks = []
                async for t in srv.stream(victim):
                    toks.append(t)
                    if len(toks) == 2:
                        assert srv.cancel(victim) is True
                return toks

            async def stream_survivor():
                return [t async for t in srv.stream(survivor)]

            v_toks, s_toks = await asyncio.gather(
                stream_victim(), stream_survivor()
            )
            # cancel is idempotent once terminal
            assert srv.cancel(victim) is False
            return v_toks, s_toks, srv.response(victim), srv.snapshot()

    v_toks, s_toks, v_resp, snap = asyncio.run(main())
    assert s_toks == baseline[1]  # survivor untouched by the cancel
    assert v_toks == baseline[0][: len(v_toks)]  # prefix of the full run
    assert len(v_toks) < len(baseline[0])
    assert v_resp["status"] == "failed"
    assert v_resp["error"]["type"] == "cancelled"
    assert v_resp["choices"][0]["finish_reason"] == "cancelled"
    assert snap["cancelled_requests"] == 1
    assert snap["active_streams"] == 0
    _audit(eng)


def test_http_transport_roundtrips(params):
    """The stdlib HTTP loop: plain + SSE completions, request lookup,
    cancel route, scrape endpoints, and 404s — one in-process socket
    client per request (``Connection: close`` framing)."""
    gen = GenerationConfig(max_new_tokens=5)
    eng = _paged(
        params, gen, PagedConfig(block_size=8, num_blocks=64)
    )
    prompt = _prompts(np.random.default_rng(4), (7,))[0]

    async def http(host, port, method, target, body=None):
        reader, writer = await asyncio.open_connection(host, port)
        payload = b"" if body is None else json.dumps(body).encode()
        writer.write(
            f"{method} {target} HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
        )
        await writer.drain()
        raw = await reader.read()
        writer.close()
        await writer.wait_closed()
        head, _, data = raw.partition(b"\r\n\r\n")
        return int(head.split()[1]), data

    async def main():
        srv = GraftServer(eng, idle_poll_s=0.002)
        host, port = await srv.serve_http()
        try:
            status, data = await http(
                host, port, "POST", "/v1/completions",
                {"prompt": prompt, "service_class": "interactive",
                 "tenant": "acme"},
            )
            assert status == 200
            resp = json.loads(data)
            assert resp["status"] == "finished"
            assert resp["service_class"] == "interactive"
            assert resp["tenant"] == "acme"
            first = resp["choices"][0]["token_ids"]
            assert len(first) == gen.max_new_tokens

            # SSE: same prompt, token events must equal the final payload
            status, data = await http(
                host, port, "POST", "/v1/completions",
                {"prompt": prompt, "stream": True},
            )
            assert status == 200
            events = [
                json.loads(line[len("data: "):])
                for line in data.decode().split("\n\n")
                if line.startswith("data: ") and line != "data: [DONE]"
            ]
            assert "data: [DONE]" in data.decode()
            toks = [e["token"] for e in events if "token" in e]
            final = [e for e in events if "choices" in e][-1]
            assert final["choices"][0]["token_ids"] == toks
            assert toks == first  # greedy determinism across requests

            status, data = await http(host, port, "GET", "/v1/requests/0")
            assert status == 200
            assert json.loads(data)["id"] == "cmpl-0"

            # cancel on an already-finished rid: 200, cancelled=false
            status, data = await http(
                host, port, "POST", "/v1/requests/0/cancel"
            )
            assert status == 200
            assert json.loads(data) == {"rid": 0, "cancelled": False}

            status, _ = await http(host, port, "GET", "/v1/requests/99")
            assert status == 404
            status, _ = await http(
                host, port, "POST", "/v1/requests/99/cancel"
            )
            assert status == 404
            status, _ = await http(host, port, "GET", "/nope")
            assert status == 404

            status, data = await http(host, port, "GET", "/snapshot")
            assert status == 200
            snap = json.loads(data)
            assert snap["finished"] == 2
            assert "requests_by_class" in snap

            status, data = await http(host, port, "GET", "/metrics")
            assert status == 200
            text = data.decode()
            assert "serving_finished 2" in text
            assert 'serving_info{kv_dtype="' in text
            assert 'serving_requests_class{class="interactive"' in text
        finally:
            await srv.close()

    asyncio.run(main())
    _audit(eng)


def test_slo_steady_state_resident_under_prewarm(params):
    """SloPolicy must not tax the device path: on a prewarmed async
    engine, steady-state decode steps do zero host→device uploads and the
    whole run compiles nothing after the prewarm freeze
    (``steadystate_compiles == 0``) — scheduling lives in action meta,
    which the device programs never see."""
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=32, num_blocks=8, prewarm=True,
            step_policy="slo",
            slo_ttft_p99_ms=50.0, slo_tpot_p99_ms=10_000.0,
            slo_eval_steps=8,
        ),
    )
    paged.submit(
        _prompts(np.random.default_rng(0), (4,))[0],
        service_class="interactive", tenant="acme",
    )
    paged.step()  # admission + prefill
    paged.step()  # first async dispatch flushes the dirty lane
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas)
        assert paged.step()
        assert (m.h2d_uploads, m.lane_syncs, m.table_deltas) == before
    paged.run_to_completion()
    assert m.prewarm_compiles > 0
    assert m.steadystate_compiles == 0
    _audit(paged)


def test_serving_load_smoke_in_process(params):
    """The load harness's tier-1 leg: burst fifo-vs-slo comparison (the
    interactive-p99-improves / throughput-within-5% acceptance gates) and
    the async streaming-client leg, sharing the suite's compile cache."""
    mod = _load_script("serving_load")
    assert mod.main(["--smoke"]) == 0
