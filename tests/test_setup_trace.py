"""The process's set-up recorder (``utils/setup_record.py`` ``SETUP``): spans
nest under the span that was open, a compile-path event is booked to the
innermost open span, both lists are bounded, the origin is the process's
start — and a running engine pays nothing for any of it."""

import os
import shutil
import subprocess
import sys
import time

import jax
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
    PagedConfig,
    PagedServingEngine,
    tracing,
)
from neuronx_distributed_llama3_2_tpu.utils import setup_record
from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP, SetupRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = LLAMA_CONFIGS["tiny"]
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# the whole vocabulary: a span is added with the metric or the INFO line that reads it
SPANS = {
    "setup.runtime", "setup.inference_engine", "setup.placement", "setup.paged_engine",
    "setup.prewarm", "setup.program", "setup.mark_steady", "setup.cost_profiles", "setup.facts",
}


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _engine(params, trace=False):
    eng = InferenceEngine(TINY, params, max_batch=2, max_seq_len=64, buckets=[16, 32])
    return PagedServingEngine(
        eng, GenerationConfig(max_new_tokens=4),
        PagedConfig(block_size=8, num_blocks=32, prewarm=True, trace_enabled=trace),
    )


def test_spans_nest_under_the_span_that_was_open():
    rec = SetupRecorder()
    with rec.span("setup.paged_engine"):
        with rec.span("setup.prewarm"):
            with rec.span("setup.program", key="a", kind="pdecode"):
                pass
            with rec.span("setup.program", key="b", kind="pctx"):
                pass
        with rec.span("setup.mark_steady"):
            pass
    with rec.span("setup.runtime"):
        pass
    names = [s[0] for s in rec.spans]
    assert names == ["setup.paged_engine", "setup.prewarm", "setup.program", "setup.program",
                     "setup.mark_steady", "setup.runtime"]
    assert [s[3] for s in rec.spans] == [None, 0, 1, 1, 0, None]
    assert rec.spans[2][4] == {"key": "a", "kind": "pdecode"}
    assert rec._open == []
    for name, t0, t1, parent, _ in rec.spans:
        assert t1 >= t0
        if parent is not None:          # a child lies inside its parent
            assert rec.spans[parent][1] <= t0 and t1 <= rec.spans[parent][2]
    # siblings never overlap
    assert rec.spans[2][2] <= rec.spans[3][1]
    assert set(names) <= SPANS


def test_an_event_is_booked_to_the_innermost_open_span_and_to_none_outside():
    rec = SetupRecorder()
    rec.on_duration(TRACE, 0.5, fun_name="init")
    with rec.span("setup.paged_engine"):
        rec.on_duration(LOWER, 0.25, fun_name="fn")
        with rec.span("setup.program", key="k", kind="pdecode"):
            rec.on_duration(TRACE, 0.125, fun_name="fn")
            rec.on_event("/jax/compilation_cache/compile_requests_use_cache")
            rec.on_event("/jax/compilation_cache/cache_hits")
        rec.on_duration("/jax/core/compile/backend_compile_duration", 1.0, fun_name="fn")
    # what no metric and no INFO line reads is not recorded
    rec.on_duration("/jax/some/other_event", 9.0)
    rec.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    rec.on_duration("/jax/compilation_cache/compile_time_saved_sec", 2.0)
    rec.on_event("/jax/compilation_cache/cache_misses")
    assert [(e[1], e[2], e[3], e[4]) for e in rec.events] == [
        ("trace", 0.5, "init", None), ("lower", 0.25, "fn", 0), ("trace", 0.125, "fn", 1),
        ("cache_request", 0.0, None, 1), ("cache_hit", 0.0, None, 1), ("compile", 1.0, "fn", 0),
    ]
    assert all(a[0] <= b[0] for a, b in zip(rec.events, list(rec.events)[1:]))


def test_nested_trace_intervals_count_once():
    assert setup_record.union_seconds([(0.0, 4.0), (1.0, 2.0), (2.5, 3.0)]) == 4.0
    assert setup_record.union_seconds([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]) == 3.0
    assert setup_record.union_seconds([]) == 0.0
    rec = SetupRecorder()
    now = time.perf_counter()
    # an inner jit's trace (1 s) reported inside its caller's (4 s), one lowering
    rec.events.extend([
        (now - 1.0, "trace", 1.0, "inner", None), (now, "trace", 4.0, "outer", None),
        (now, "lower", 0.5, "outer", None),
    ])
    assert rec.summary()["trace_lower_s"] == pytest.approx(4.5)       # the sum would say 5.5


def test_both_records_are_bounded(monkeypatch):
    monkeypatch.setattr(setup_record, "SETUP_RECORD_MAX", 4)
    rec = SetupRecorder()
    assert rec.events.maxlen == 4
    for i in range(10):
        rec.on_duration(TRACE, 0.1, fun_name=str(i))
        with rec.span("setup.program", key=i):
            pass
    assert len(rec.events) == 4 and [e[3] for e in rec.events] == ["6", "7", "8", "9"]
    assert len(rec.spans) == 4 and rec._open == []
    assert SETUP.events.maxlen == 1 << 15 and tracing.SETUP is SETUP


def test_origin_is_the_process_start_and_falls_back_to_the_import_instant(monkeypatch):
    origin, is_import = setup_record._process_start()
    now = time.perf_counter()
    assert is_import is False
    # this process began before the recorder was imported, and not days ago
    assert origin < SETUP.origin + 0.011 and 0 < now - origin < 86400
    assert SETUP.origin_is_import is False
    # against ps's reading of the same process, to the clock tick
    if shutil.which("ps"):
        etimes = subprocess.run(
            ["ps", "-o", "etimes=", "-p", str(os.getpid())], capture_output=True, text=True,
        ).stdout.strip()
        assert abs((time.perf_counter() - origin) - float(etimes)) < 2.5

    def no_proc(*a, **k):
        raise OSError("no /proc here")

    monkeypatch.setattr("builtins.open", no_proc)
    before = time.perf_counter()
    origin, is_import = setup_record._process_start()
    assert is_import is True and before <= origin <= time.perf_counter()


def test_summary_gives_seconds_by_span_name_and_the_compile_paths_sums():
    rec = SetupRecorder()
    rec.origin = time.perf_counter() - 30.0
    o = rec.origin
    rec.spans = [
        ["setup.runtime", o + 3.0, o + 8.0, None, {}],
        ["setup.inference_engine", o + 10.0, o + 11.0, None, {}],
        ["setup.placement", o + 10.25, o + 10.75, 1, {}],
        ["setup.paged_engine", o + 12.0, o + 22.0, None, {}],
        ["setup.prewarm", o + 13.0, o + 18.0, 3, {}],
        ["setup.program", o + 13.0, o + 15.0, 4, {"key": "a"}],
        ["setup.program", o + 15.0, o + 18.0, 4, {"key": "b"}],
        ["setup.paged_engine", o + 25.0, None, None, {}],       # still open: not summed
    ]
    rec.events.extend([
        (o + 14.0, "compile", 0.5, "fn", 5), (o + 16.0, "compile", 0.25, "fn", 6),
        (o + 14.0, "cache_request", 0.0, None, 5), (o + 16.0, "cache_request", 0.0, None, 6),
        (o + 16.0, "cache_hit", 0.0, None, 6),
    ])
    table = rec.summary()
    assert 30.0 <= table.pop("since_start_s") < 31.0
    assert table == pytest.approx({
        "before_runtime_s": 3.0, "setup.runtime": 5.0, "setup.inference_engine": 1.0,
        "setup.placement": 0.5, "setup.paged_engine": 10.0, "setup.prewarm": 5.0,
        "setup.program": 5.0, "trace_lower_s": 0.0, "compile_s": 0.75, "cache_misses": 1,
    })


def test_importing_the_package_does_not_start_the_backend():
    """``setup.runtime`` is the first ``jax.devices()``: a module that builds a
    jax array as it is imported would start the backend under no span."""
    code = (
        "from jax._src import xla_bridge as xb\n"
        "import neuronx_distributed_llama3_2_tpu.serving, neuronx_distributed_llama3_2_tpu.trainer\n"
        "from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP\n"
        "assert not xb.backends_are_initialized() and not SETUP.spans\n"
        "from neuronx_distributed_llama3_2_tpu.utils.runtime import device_summary\n"
        "device_summary(); device_summary()\n"
        "assert [s[0] for s in SETUP.spans] == ['setup.runtime'], SETUP.spans\n"
        "assert xb.backends_are_initialized() and SETUP.summary()['before_runtime_s'] > 0\n"
    )
    _run(code)


def _run(code):
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_the_recorder_lies_below_serving():
    """The runtime, the dense engine and the trainer's process write the record
    without loading the serving package."""
    _run(
        "import sys\n"
        "from neuronx_distributed_llama3_2_tpu.utils.runtime import device_summary\n"
        "import neuronx_distributed_llama3_2_tpu.trainer, neuronx_distributed_llama3_2_tpu.inference.placement\n"
        "device_summary()\n"
        "from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP\n"
        "assert [s[0] for s in SETUP.spans] == ['setup.runtime']\n"
        "assert 'neuronx_distributed_llama3_2_tpu.serving' not in sys.modules\n"
    )


def test_two_engines_one_recorder_and_a_steady_engine_adds_nothing(params):
    from jax._src import monitoring

    spans_before = len(SETUP.spans)
    first, second = _engine(params), _engine(params)
    assert [cb for cb in monitoring.get_event_duration_listeners() if cb == SETUP.on_duration] == [SETUP.on_duration]
    assert [cb for cb in monitoring.get_event_listeners() if cb == SETUP.on_event] == [SETUP.on_event]
    new = SETUP.spans[spans_before:]
    names = [s[0] for s in new]
    assert names.count("setup.paged_engine") == 2 and names.count("setup.inference_engine") == 2
    assert "setup.facts" not in names and set(names) <= SPANS
    # the phases are the paged engine's children, siblings of one another
    roots = {spans_before + i for i, s in enumerate(new) if s[0] == "setup.paged_engine"}
    for s in new:
        if s[0] in ("setup.prewarm", "setup.mark_steady", "setup.cost_profiles"):
            assert s[3] in roots
    # every catalog key has its span, under setup.prewarm, and the events a program
    # raised carry its index: that is what names a `jit(fn)`
    programs = [spans_before + i for i, s in enumerate(new) if s[0] == "setup.program"]
    assert len(programs) == len(first.catalog.prewarm_keys()) + len(second.catalog.prewarm_keys())
    assert all(SETUP.spans[SETUP.spans[i][3]][0] == "setup.prewarm" for i in programs)
    assert {SETUP.spans[i][4]["kind"] for i in programs} >= {"pctx", "psfx", "pdecode"}
    traced = {e[4] for e in SETUP.events if e[1] == "trace" and e[3] == "fn"}
    assert traced & set(programs)
    table = SETUP.summary()
    assert table["setup.prewarm"] > 0 and table["trace_lower_s"] > 0

    # the first admissions run two small host-side programs prewarm does not cover (the
    # per-request key's split): they are on record, under no span, and no serving program is
    def drive(steps):
        for i in range(steps):
            if not first.step():
                first.submit([7 + i % 5, 8, 9, 10])

    for prompt in ([5, 6, 7, 8, 9], [11, 12, 13], [3, 4, 5, 6, 7, 8, 9, 10, 11]):
        first.submit(prompt)
    n_spans, n_events = len(SETUP.spans), len(SETUP.events)
    drive(20)
    late = list(SETUP.events)[n_events:]
    assert all(e[4] is None and e[3] != "fn" for e in late)
    # 50 steps of the warmed engine under traffic: no span, no event
    n_events = len(SETUP.events)
    drive(50)
    assert first.metrics.steadystate_compiles == 0
    assert len(SETUP.spans) == n_spans and len(SETUP.events) == n_events


def test_a_traced_engine_carries_the_record_beside_its_facts(params):
    spans_before = len(SETUP.spans)
    traced = _engine(params, trace=True)
    setup = traced.tracer.timeline()["setup"]
    assert {"relaid_leaves", "relaid_bytes", "program_temp_bytes_max", "cache_row_bytes"} <= set(setup)
    assert {"origin", "origin_is_import", "spans", "events"} <= set(setup)
    assert setup["origin"] == SETUP.origin and len(setup["spans"]) == len(SETUP.spans)
    assert isinstance(setup["spans"], list) and isinstance(setup["events"][0], list)
    new = [s[0] for s in setup["spans"][spans_before:]]
    assert new.count("setup.facts") == 1
    # the deep harvest's compile-path events are booked to setup.facts
    facts = spans_before + new.index("setup.facts")
    assert any(e[4] == facts for e in setup["events"])
    setup_events = [e for e in traced.tracer.chrome_events() if e["pid"] == 3]
    assert setup_events[0]["ph"] == "M" and setup_events[0]["args"] == {"name": "setup"}
    slices = [e for e in setup_events if e["ph"] == "X"]
    assert {"setup.paged_engine", "setup.prewarm", "setup.program", "setup.facts"} <= {e["name"] for e in slices}
    assert all(e["dur"] >= 0 and e["cat"] == "setup" for e in slices)
    # an untraced engine's timeline stays empty
    assert _engine(params).tracer.timeline()["setup"] == {}
