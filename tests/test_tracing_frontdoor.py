"""graftscope's front-door recorder and its one clock (serving/tracing.py).

A tiny CPU engine behind ``GraftServer.serve_http``: one streamed request
yields ``request ⊇ door.read, door.submit, queued, prefilling, active,
door.first_write`` in order under one rid; ``drive.step/pump/yield`` tile the
driver loop; with tracing off nothing is recorded and no ``TraceAnnotation`` is
entered; with it on, uploads, program registry and tokens are those of an
untraced engine; and every traced step lands in a profile as ``graft.step``
with its index, which is what joins the tracer's clock to the device trace's.
"""

import asyncio
import glob
import json
import math
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
from neuronx_distributed_llama3_2_tpu.serving import (
    EngineTracer,
    GraftServer,
    PagedConfig,
    PagedServingEngine,
)
from neuronx_distributed_llama3_2_tpu.serving import tracing

from benchmarks import program_trace
from tests.test_paged_serving import _prompts

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _paged(params, gen, **paged_cfg):
    eng = InferenceEngine(TINY, params, max_batch=4, max_seq_len=64, buckets=[8, 16, 32])
    return PagedServingEngine(eng, gen, PagedConfig(block_size=8, num_blocks=64, **paged_cfg))


async def _stream_completion(host, port, prompt):
    """POST one streamed completion; the token ids as they arrived."""
    reader, writer = await asyncio.open_connection(host, port)
    body = json.dumps({"prompt": prompt, "stream": True}).encode()
    writer.write(
        f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
        f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n".encode() + body
    )
    await writer.drain()
    await reader.readuntil(b"\r\n\r\n")
    tokens = []
    while True:
        event = (await reader.readuntil(b"\n\n")).decode().strip()
        if event == "data: [DONE]":
            break
        payload = json.loads(event[len("data: "):])
        if "token" in payload:
            tokens.append(payload["token"])
    writer.close()
    return tokens


def _serve(eng, prompts):
    """Serve ``prompts`` over HTTP/SSE, all at once; tokens per prompt."""

    async def main():
        server = GraftServer(eng, idle_poll_s=0.002)
        host, port = await server.serve_http()
        out = await asyncio.gather(*(_stream_completion(host, port, p) for p in prompts))
        await asyncio.sleep(0.01)        # the handlers' `finally` (request closed)
        await server.close()
        return out

    return asyncio.run(main())


@pytest.fixture(scope="module")
def served(params):
    """One traced request through the whole front door, and its timeline."""
    eng = _paged(params, GenerationConfig(max_new_tokens=5),
                 trace_enabled=True, prefill_chunk_tokens=8)
    prompt = _prompts(np.random.default_rng(2), (19,))[0]     # three prefill chunks
    tokens = _serve(eng, [prompt])[0]
    return eng, tokens, eng.tracer.timeline()


def test_one_request_yields_the_door_spans_in_order_under_one_rid(served):
    eng, tokens, tl = served
    assert len(tokens) == 5
    (door,) = [d for d in tl["requests"] if d["rid"] is not None]
    rid = door["rid"]
    names = [n for n, _, _ in door["spans"]]
    assert names == ["door.read", "door.submit", "door.first_write"]
    read, submit, write = (program_trace.door_span(door, n) for n in names)
    states = tl["states"][rid]
    assert [s for _, s in states] == ["queued", "prefilling", "active", "finished"]
    queued, prefilling, active, finished = (ts for ts, _ in states)
    (first_token,) = [(ts, args) for name, ts, r, args in tl["marks"]
                      if name == "first_token" and r == rid]
    # accepted -> read -> submit (queued inside it) -> admitted -> first token
    # committed -> active -> pumped -> first chunk written -> ... -> closed
    order = [door["t0"], read[1], queued, submit[1], prefilling, first_token[0], active,
             write[0], write[1], finished, door["t1"]]
    assert order == sorted(order), order
    assert read[0] == door["t0"] and submit[0] == read[1]         # children abut
    assert all(door["t0"] <= a <= b <= door["t1"] for _, a, b in door["spans"])
    # the legs add up to the root's head: accepted -> first chunk written
    legs = [submit[1] - door["t0"], prefilling - submit[1], first_token[0] - prefilling,
            write[1] - first_token[0]]
    assert sum(legs) == pytest.approx(write[1] - door["t0"], abs=1e-9)
    # the mark names the engine step that committed the token
    step = next(s for s in tl["steps"] if s["step"] == first_token[1]["step"])
    assert step["t0"] <= first_token[0] <= step["t1"]
    # prefill chunks carry the rid, under their step
    chunks = [args for s in tl["steps"] for ph, n, _, _, args in s["events"] if n == "prefill_chunk"]
    assert len(chunks) == 3 and all(c["rid"] == rid for c in chunks)
    assert not eng.tracer._doors                      # closed roots leave the live index


def test_the_readers_legs_are_those_spans(served):
    eng, _, tl = served
    (door,) = [d for d in tl["requests"] if d["rid"] is not None]
    result = {"kind": "serving", "serving": eng, "window": (door["t0"] - 1, door["t0"] + 1),
              "in_window": [], "notes": []}
    legs = program_trace.ttft_legs(result)
    assert {k: len(v) for k, v in legs.items()} == dict.fromkeys(legs, 1)
    parts = sum(legs[k][0] for k in ("pre_submit", "queue", "admit_to_first_token", "first_write"))
    # queued starts inside door.submit, so the parts overlap the total by that sliver
    assert parts == pytest.approx(legs["total"][0], abs=0.5)
    assert any(n.startswith("ttft legs") for n in result["notes"])


def test_drive_spans_tile_the_loop_with_no_overlap(served):
    _, _, tl = served
    turns = [t for t in tl["drive"] if t[0] is not None]
    assert len(turns) >= 5
    for step, t0, t1, t2, t3 in turns:
        assert t0 <= t1 <= t2 <= t3                   # step | pump | yield abut
    for a, b in zip(tl["drive"], tl["drive"][1:]):
        assert a[4] <= b[1]                           # a turn starts after the last ended
        assert b[1] - a[4] < 5e-3                     # and nothing sits between turns
    # drive.step is the parent of the engine's step record of the same index
    by_index = {s["step"]: s for s in tl["steps"]}
    for step, t0, t1, _, _ in turns:
        assert t0 <= by_index[step]["t0"] and by_index[step]["t1"] <= t1
    assert any(t[0] is None for t in tl["drive"])     # the parked loop is drive.idle


def test_export_holds_the_new_names_and_no_counter_event(served, tmp_path):
    eng, _, _ = served
    with open(eng.export_trace(str(tmp_path / "t.json"))) as f:
        events = json.load(f)["traceEvents"]
    assert not [e for e in events if e["ph"] == "C"]
    assert not hasattr(EngineTracer, "counter")
    names = {e["name"] for e in events}
    assert {"request", "door.read", "door.submit", "door.first_write", "first_token",
            "drive.step", "drive.pump", "drive.yield", "drive.idle", "queued", "dispatch"} <= names
    door = [e for e in events if e.get("cat") == "door"]
    assert len({e["tid"] for e in door}) == 1 and all(e["pid"] == 1 for e in door)
    assert all(e["args"].get("parent") == "request" for e in door if e["name"] != "request")


class _CountingAnnotation:
    entered = 0

    def __init__(self, *a, **k):
        pass

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


def test_tracing_off_records_nothing_and_enters_no_annotation(params, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.entered = 0
    eng = _paged(params, GenerationConfig(max_new_tokens=4))
    prompts = _prompts(np.random.default_rng(5), (6, 9))
    assert all(len(t) == 4 for t in _serve(eng, prompts))
    assert _CountingAnnotation.entered == 0
    tl = eng.tracer.timeline()
    assert tl == {"steps": [], "requests": [], "states": {}, "marks": [], "drive": [],
                  "setup": {}, "routed": [], "routed_local": []}
    assert eng.tracer.open_request() is None and eng.tracer._conn == 0
    # on: one annotation a step, each closed again
    eng_on = _paged(params, GenerationConfig(max_new_tokens=4), trace_enabled=True)
    _serve(eng_on, prompts)
    assert _CountingAnnotation.entered == len(eng_on.tracer.timeline()["steps"]) > 0
    assert eng_on.tracer._annotation is None


def test_server_hooks_change_no_uploads_no_programs_no_tokens(params):
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(np.random.default_rng(7), (5, 12, 9, 3))

    def run(trace):
        eng = _paged(params, gen, trace_enabled=trace)
        out = _serve(eng, prompts)
        m = eng.metrics
        return out, (m.h2d_uploads > 0, m.steadystate_compiles), sorted(map(str, eng._programs))

    out_off, counts_off, progs_off = run(False)
    out_on, counts_on, progs_on = run(True)
    assert out_on == out_off
    assert counts_on == counts_off and progs_on == progs_off


def test_front_door_records_are_bounded():
    tr = EngineTracer(enabled=True, buffer_steps=4, max_requests=3)
    for i in range(10):
        door = tr.open_request()
        tr.bind_request(door, i)
        tr.mark("first_token", i, step=i)
        tr.drive_turn(i, 0.0, 1.0, 2.0, 3.0)
        tr.close_request(door)
    tl = tr.timeline()
    assert [d["rid"] for d in tl["requests"]] == [7, 8, 9]
    assert len(tl["marks"]) == 3 and len(tl["drive"]) == 4 and not tr._doors


def test_a_step_that_raised_leaves_no_annotation_open():
    tr = EngineTracer(enabled=True)
    tr.begin_step(1)
    first = tr._annotation
    tr.begin_step(2)                     # step 1 never reached end_step
    assert tr._annotation is not first
    tr.end_step()
    assert tr._annotation is None and [s["step"] for s in tr._steps] == [2]


def test_every_traced_step_lands_in_a_profile_with_its_index(params, tmp_path):
    """The one clock: ``graft.step`` events of a profile (here the host's own)
    join to the tracer's step records by index, and the join's error is what
    sits between two consecutive lines of ``begin_step``."""
    eng = _paged(params, GenerationConfig(max_new_tokens=8), trace_enabled=True)
    prompts = _prompts(np.random.default_rng(3), (5, 7))
    for p in prompts:
        eng.submit(p)
    eng.step()                                        # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    eng.run_to_completion()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    planes = program_trace.read(path)
    seen = program_trace.annotations_of(planes, tracing.STEP_ANNOTATION)
    steps = eng.tracer.timeline()["steps"]
    profiled = [s["step"] for s in steps][1:]
    assert [step for step, _, _ in seen] == profiled and len(profiled) >= 5
    join = program_trace.clock_join(seen, steps)
    assert join["steps"] == len(profiled)
    assert join["error_us"] < 100, join
    # on the joined clock an annotation covers its step record's body
    by_index = {s["step"]: s for s in steps}
    for step, a, b in seen:
        rec = by_index[step]
        assert abs((rec["t0"] + join["offset"]) - a) < 200e-6
        assert b <= rec["t1"] + join["offset"] + 200e-6


def test_tracing_overhead_smoke_through_the_server(params):
    """tests/test_tracing.py's overhead smoke, through the server's hooks: host
    time per step with tracing on stays within 5 % (+0.3 ms against CPU jitter)
    of tracing off; the least of six warm rounds a side, alternating between
    the two engines so that the machine's load falls on both."""
    gen = GenerationConfig(max_new_tokens=12)
    prompts = _prompts(np.random.default_rng(4), (6, 9))

    def round_ms(eng):
        h0, s0 = eng.metrics.host_schedule_ms, eng.metrics.decode_steps
        _serve(eng, prompts)
        return (eng.metrics.host_schedule_ms - h0) / max(eng.metrics.decode_steps - s0, 1)

    engines = {trace: _paged(params, gen, trace_enabled=trace) for trace in (False, True)}
    for eng in engines.values():
        for _ in range(2):  # the first two rounds compile
            round_ms(eng)
    best = {False: math.inf, True: math.inf}
    for _ in range(6):
        for trace, eng in engines.items():
            best[trace] = min(best[trace], round_ms(eng))
    off, on = best[False], best[True]
    assert on <= off * 1.05 + 0.3, (on, off)
