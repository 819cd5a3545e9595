"""graftscope: histogram math, tracer mechanics, and the engine contracts.

Three layers under test (docs/serving.md "Observability"):

- :class:`~neuronx_distributed_llama3_2_tpu.serving.Histogram` /
  :class:`~...serving.EngineTracer` unit behavior (no engine, no jax);
- the engine contracts: request_info timing fields survive into terminal
  records, ``snapshot()`` keeps its golden key set, ``prometheus()``
  renders valid exposition, the dashboard renders a snapshot;
- **zero interference**: with ``trace_enabled`` the engine's greedy
  outputs, h2d upload counts, and program registry are identical to the
  untraced engine across {sync,async} x {gather,kernel}, the steady-state
  step stays fully resident, and a 200+-step mixed soak (chunked prefill
  + speculation + async + injected faults) exports a valid Chrome trace
  carrying per-request spans, ProgramRecord-tagged dispatch slices, and
  fault/degradation instants.
"""

import dataclasses
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
from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import audit_programs
from neuronx_distributed_llama3_2_tpu.serving import (
    EngineTracer,
    FaultInjector,
    FaultPlan,
    Histogram,
    PagedConfig,
    PagedServingEngine,
    audit_engine,
    program_label,
)

from tests.drained_policy import LOOPS, loop_policy
from tests.test_paged_serving import _dense_outputs, _prompts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _paged(params, gen, paged_cfg, model_cfg=TINY, injector=None, policy=None):
    eng = InferenceEngine(
        model_cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16, 32]
    )
    return PagedServingEngine(
        eng, gen, paged_cfg, injector=injector, policy=policy
    )


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------


def test_histogram_counts_mean_max_and_clamping():
    h = Histogram(1.0, 64.0, 2.0)
    for v in (0.5, 3.0, 10.0, 100.0):  # 100 > hi lands in overflow
        h.observe(v)
    assert h.count == 4
    assert h.max == 100.0
    assert h.mean() == pytest.approx(113.5 / 4)
    h.observe(-5.0)           # negative clamps to 0, still counted
    h.observe(float("nan"))   # NaN clamps to 0, still counted
    assert h.count == 6 and h.max == 100.0


def test_histogram_percentiles_monotonic_and_log_bounded():
    h = Histogram(0.05, 8e5, 2.0)  # the engine's ms bucket spec
    vals = np.random.default_rng(0).lognormal(mean=2.0, sigma=1.0, size=500)
    for v in vals:
        h.observe(float(v))
    p50, p90, p99 = h.percentile(0.5), h.percentile(0.9), h.percentile(0.99)
    assert 0 < p50 <= p90 <= p99 <= h.max
    # estimate and true quantile share a bucket, so the ratio is bounded
    # by the growth factor
    true50 = float(np.percentile(vals, 50))
    assert true50 / 2.0 <= p50 <= true50 * 2.0


def test_histogram_overflow_bucket_reports_max():
    h = Histogram(1.0, 8.0, 2.0)
    for v in (100.0, 200.0, 300.0):
        h.observe(v)
    assert h.percentile(0.5) == 300.0
    assert set(h.snapshot()) == {"count", "mean", "max", "p50", "p90", "p99"}


def test_histogram_prometheus_block():
    h = Histogram(1.0, 8.0, 2.0)  # finite edges 1, 2, 4, 8
    for v in (0.5, 3.0, 100.0):
        h.observe(v)
    lines = h.prometheus_lines("x_ms")
    assert lines[0] == "# TYPE x_ms histogram"
    assert 'x_ms_bucket{le="1"} 1' in lines
    assert 'x_ms_bucket{le="4"} 2' in lines    # cumulative; zero le="2" elided
    assert 'x_ms_bucket{le="+Inf"} 3' in lines
    assert lines[-2] == "x_ms_sum 103.5"
    assert lines[-1] == "x_ms_count 3"


# ---------------------------------------------------------------------------
# EngineTracer
# ---------------------------------------------------------------------------


def test_tracer_disabled_records_nothing():
    tr = EngineTracer(enabled=False)
    tr.begin_step(0)
    with tr.phase("admit"):
        pass
    tr.complete("dispatch", 0.0, 1.0)
    tr.instant("fault")
    tr.request_state(0, "queued")
    tr.end_step()
    assert tr.phase("a") is tr.phase("b")  # shared no-op span, no allocation
    assert all(e["ph"] == "M" for e in tr.chrome_events())  # metadata only


def test_tracer_ring_buffer_bounds_memory():
    tr = EngineTracer(enabled=True, buffer_steps=4)
    for i in range(10):
        tr.begin_step(i)
        tr.complete("dispatch", tr.now())
        tr.end_step(queue=0)
    steps = [e for e in tr.chrome_events() if e.get("cat") == "step"]
    assert [e["args"]["step"] for e in steps] == [6, 7, 8, 9]


def test_tracer_request_spans_and_terminal_retirement():
    tr = EngineTracer(enabled=True)
    for state in ("queued", "prefilling", "active", "finished"):
        tr.request_state(3, state)
    tr.request_state(4, "queued")  # still live
    evs = [e for e in tr.chrome_events() if e.get("tid") == 3 and e["ph"] != "M"]
    assert [e["name"] for e in evs] == ["queued", "prefilling", "active",
                                       "finished"]
    assert [e["ph"] for e in evs] == ["X", "X", "X", "i"]
    # each state slice ends where the next begins (abutting timeline)
    assert evs[0]["ts"] + evs[0]["dur"] == pytest.approx(evs[1]["ts"], abs=0.2)
    assert 3 not in tr._spans and 4 in tr._spans  # terminal span retired


def test_tracer_export_formats(tmp_path):
    tr = EngineTracer(enabled=True)
    tr.begin_step(0)
    tr.instant("fault", kind="device")
    tr.end_step()
    p = tr.export(str(tmp_path / "t.json"))
    with open(p) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    pj = tr.export(str(tmp_path / "t.jsonl"), fmt="jsonl")
    with open(pj) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == len(doc["traceEvents"])
    with pytest.raises(ValueError, match="unknown trace format"):
        tr.export(str(tmp_path / "t.bin"), fmt="binary")


def test_program_label_renders_kind_and_sorted_meta():
    class R:
        kind = "pdecode"
        meta = {"kv_limit": 8, "gather": False}

    assert program_label(R()) == "pdecode[gather=False,kv_limit=8]"


# ---------------------------------------------------------------------------
# engine contracts: one shared finished engine for the cheap checks
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def done_engine(params):
    gen = GenerationConfig(max_new_tokens=6)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=32, trace_enabled=True),
    )
    for p in _prompts(np.random.default_rng(2), (10, 5)):
        paged.submit(p)
    paged.run_to_completion()
    return paged


def test_request_info_timing_survives_into_finished_records(done_engine):
    info = done_engine.request_info(0)
    assert info["status"] == "finished"
    assert info["ttft_ms"] > 0
    assert info["tpot_ms"] > 0          # 6 tokens => 5 inter-token intervals
    assert info["queue_ms"] >= 0
    assert info["prefill_ms"] > 0
    assert info["finished_at"] >= info["first_token_at"] >= info["submitted_at"]


# the stable snapshot schema: dashboards, the metrics_log_every jsonl, and
# the bench records all consume these keys — additions extend this set,
# renames/removals are breaking and must be deliberate
EXPECTED_SNAPSHOT_KEYS = {
    # dataclass counters
    "submitted", "admitted", "admit_blocked", "finished", "truncated",
    "preemptions", "state_resets", "state_kernel_steps", "sparse_kernel_chunks", "decode_steps", "engine_steps",
    "compute_dispatches",
    "mixed_dispatches", "prefill_tokens", "prefill_chunks",
    "cached_tokens", "decode_steps_async", "lame_duck_tokens",
    # why a decode step was not dispatched ahead (PR 34: the pool reason
    # is the former sync_fallbacks, renamed with its five siblings)
    "lookahead_declined_spec", "lookahead_declined_ladder",
    "lookahead_declined_admit", "lookahead_declined_prefill",
    "lookahead_declined_finish", "lookahead_declined_pool",
    "lane_syncs", "table_deltas", "h2d_uploads",
    "host_schedule_ms", "device_wait_ms", "tp_size", "kv_dtype",
    "pool_bytes_per_rank", "pool_bytes_total", "window_pool_blocks", "draft_tokens",
    "accepted_tokens", "verify_steps", "spec_disabled_lanes",
    # tree speculation (PagedConfig.spec_tree)
    "tree_verify_steps", "tree_draft_tokens", "tree_accept_by_shape",
    "faults_injected", "failed_requests", "lane_quarantines",
    "drafter_faults", "degradation_level", "degradations",
    "audit_violations", "programs_compiled", "prewarm_compiles",
    "steadystate_compiles",
    # tiered KV storage (host-RAM spill tier)
    "blocks_spilled", "blocks_restored", "spill_bytes", "restore_bytes",
    "restore_hits", "restore_fallbacks", "restore_declined",
    "restore_uploads",
    # fused on-device sampling
    "sampled_steps", "host_sample_fallbacks", "rng_reseeds",
    # graftmeter: pad-waste / dispatch-cost counters + cost-ledger gauges
    "decode_pad_tokens", "decode_need_tokens", "prefill_pad_tokens",
    "prefill_need_tokens", "dispatched_flops", "dispatched_bytes",
    "decode_pad_by_rung", "prefill_pad_by_rung", "cost_profiled_programs",
    "hbm_budget_bytes", "hbm_footprint_bytes", "hbm_headroom_bytes",
    "peak_flops_per_chip", "peak_hbm_bw_per_chip", "mfu_by_rung",
    "slo_alerts", "slo_burn_ttft", "slo_burn_tpot",
    # graftserve: front-door gauges + per-class lifecycle/burn tables
    "queued_requests", "active_streams", "cancelled_requests",
    "requests_by_class", "slo_burn_by_class",
    # graftplan: certified policy-table gauges
    "policy_table_id", "policy_table_stale", "policy_simulated_burn",
    # derived
    "prefix_skip_fraction", "accept_rate", "host_schedule_ms_per_step",
    "device_wait_ms_per_step", "dispatches_per_step", "restore_hit_rate",
    # graftmeter derived
    "pad_waste_frac", "decode_pad_frac", "prefill_pad_frac",
    "achieved_flops_per_s", "mfu_est", "bandwidth_util_est",
    # latency histogram summaries
    "ttft_ms", "tpot_ms", "step_latency_ms", "accept_len", "queue_depth",
    # allocator stats (host-tier gauges zero-default when spill is off)
    "num_blocks", "block_size", "active_blocks", "cached_blocks",
    "free_blocks", "block_utilization", "evictions", "cow_copies",
    "host_tier_bytes", "host_tier_budget_bytes", "host_tier_entries",
    "host_tier_evictions",
    # radix index
    "prefix_hit_rate", "radix_nodes", "spilled_nodes",
}


def test_snapshot_golden_keys(done_engine):
    snap = done_engine.metrics.snapshot(
        done_engine.allocator, done_engine.index
    )
    assert set(snap) == EXPECTED_SNAPSHOT_KEYS
    for key in ("ttft_ms", "tpot_ms", "step_latency_ms"):
        assert set(snap[key]) == {"count", "mean", "max", "p50", "p90", "p99"}
        assert snap[key]["count"] > 0
    json.dumps(snap)  # one JSON object, like every other metrics record


def test_prometheus_exposition(done_engine):
    text = done_engine.metrics.prometheus(
        done_engine.allocator, done_engine.index
    )
    assert text.startswith('serving_info{kv_dtype="bf16"} 1\n')
    assert "# TYPE serving_finished counter" in text
    assert "# TYPE serving_degradation_level gauge" in text
    assert "# TYPE serving_block_utilization gauge" in text
    assert "# TYPE serving_ttft_ms histogram" in text
    assert 'serving_ttft_ms_bucket{le="+Inf"} ' in text
    assert "serving_ttft_ms_count " in text
    assert text.endswith("\n")


def test_dashboard_renders_snapshot(done_engine):
    spec = importlib.util.spec_from_file_location(
        "serving_dashboard_mod",
        os.path.join(REPO, "scripts", "serving_dashboard.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    snap = done_engine.metrics.snapshot(
        done_engine.allocator, done_engine.index
    )
    text = mod.render_snapshot(snap)
    assert "ttft" in text and "p50" in text
    assert f"finished {snap['finished']}" in text
    # fused-step panel row: dispatches per engine step + the pmixed count
    assert f"dispatch   {snap['dispatches_per_step']}/step" in text
    assert f"mixed {snap['mixed_dispatches']})" in text


# ---------------------------------------------------------------------------
# zero interference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parity(params):
    gen = GenerationConfig(max_new_tokens=6)
    prompts = _prompts(np.random.default_rng(7), (5, 12, 9, 3))
    return gen, prompts, _dense_outputs(params, prompts, gen)


@pytest.mark.parametrize("model_cfg", [TINY, TINY_KERNEL],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("loop", LOOPS)
def test_tracing_on_parity_matrix(params, parity, model_cfg, loop):
    """Tracing enabled must be invisible to the decode math: greedy outputs
    identical to the dense reference, clean invariant audit, and a clean
    graftcheck program audit (GC003: no host transfers in any trace)."""
    gen, prompts, dense = parity
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=64,
                    trace_enabled=True, trace_buffer_steps=64),
        model_cfg, policy=loop_policy(loop),
    )
    for p in prompts:
        paged.submit(p)
    assert paged.run_to_completion() == dense
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []
    # the flight recorder actually recorded
    assert any(e["name"] == "dispatch"
               for e in paged.tracer.chrome_events())


def test_tracing_changes_no_uploads_and_no_programs(params, parity):
    """The hard zero-interference counters: identical h2d upload /
    lane-sync / table-delta counts and an identical program-registry key
    set, traced vs untraced (kernel + async, the fullest path)."""
    gen, prompts, dense = parity

    def run(trace):
        paged = _paged(
            params, gen,
            PagedConfig(block_size=8, num_blocks=64,
                        trace_enabled=trace),
            TINY_KERNEL,
        )
        for p in prompts:
            paged.submit(p)
        out = paged.run_to_completion()
        m = paged.metrics
        return out, (m.h2d_uploads, m.lane_syncs, m.table_deltas), \
            sorted(map(str, paged._programs))

    out_off, counts_off, progs_off = run(False)
    out_on, counts_on, progs_on = run(True)
    assert out_on == out_off == dense
    assert counts_on == counts_off
    assert progs_on == progs_off


@pytest.mark.parametrize("loop", LOOPS)
def test_steady_state_stays_resident_with_tracing_on(params, loop):
    """The zero-upload steady state (tests/test_async_serving.py) must hold
    unchanged with the flight recorder running."""
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=32, num_blocks=8, trace_enabled=True),
        policy=loop_policy(loop),
    )
    paged.submit(_prompts(np.random.default_rng(0), (4,))[0])
    paged.step()
    paged.step()
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas)
        assert paged.step()
        assert (m.h2d_uploads, m.lane_syncs, m.table_deltas) == before
    paged.run_to_completion()


def test_tracing_overhead_smoke(params):
    """What the recorder may cost is read as counts, not as CPU milliseconds
    (ROADMAP D0): over two rounds of the same prompts — the second on warm
    programs and a filled prefix cache — a traced engine emits the same
    tokens in the same number of steps and model-program dispatches, with
    the same uploads, lane syncs and table deltas, as an untraced one."""
    gen = GenerationConfig(max_new_tokens=12)
    prompts = _prompts(np.random.default_rng(4), (6, 9))

    def rounds(trace):
        paged = _paged(
            params, gen,
            PagedConfig(block_size=8, num_blocks=32, trace_enabled=trace),
        )
        outs = []
        for _ in range(2):
            rids = [paged.submit(p) for p in prompts]
            done = paged.run_to_completion()
            outs.append([done[r] for r in rids])
        m = paged.metrics
        return (outs, (m.h2d_uploads, m.lane_syncs, m.table_deltas),
                (m.engine_steps, m.compute_dispatches))

    on, off = rounds(True), rounds(False)
    assert on == off and off[2][1] > 0


# ---------------------------------------------------------------------------
# the acceptance soak: 200+ steps, every serving feature, faults, export
# ---------------------------------------------------------------------------


# tier-1 budget: every contract this acceptance soak spans (trace
# validity, phase coverage, export) has a dedicated in-tier test above;
# the 200-step all-features run rides the slow tier
@pytest.mark.slow
def test_mixed_soak_exports_valid_chrome_trace(params, tmp_path):
    rng = np.random.default_rng(1234)
    gen = GenerationConfig(max_new_tokens=14)
    cfg = PagedConfig(
        block_size=4, num_blocks=24, decode_reserve_blocks=1,
        prefill_chunk_tokens=8, spec_draft_tokens=4,
        trace_enabled=True, trace_buffer_steps=512,
        degrade_after_faults=2, degrade_window_steps=64,
        degrade_recover_steps=16,
    )
    n_requests = 18
    lengths = rng.integers(3, 32, size=n_requests)
    prompts = []
    for i, n in enumerate(lengths):
        if i % 2 == 0:  # repetitive half so speculation engages
            pat = rng.integers(1, 9, size=3).tolist()
            prompts.append((pat * (int(n) // 3 + 1))[: int(n)])
        else:
            prompts.append(
                rng.integers(0, TINY.vocab_size, size=(int(n),)).tolist()
            )
    arrivals = np.sort(rng.integers(0, 190, size=n_requests)).tolist()
    arrivals[-1] = 205  # pin one straggler so the soak spans 200+ steps
    # scheduled faults inside one degradation window: the second climbs
    # the ladder, so the trace must carry both fault instants and a
    # degradation instant; the device fault yields a failed request
    inj = FaultInjector(FaultPlan(
        seed=7,
        schedule=((5, "device"), (8, "drafter"), (10, "alloc")),
    ))
    paged = _paged(params, gen, cfg, TINY_KERNEL, injector=inj)

    steps, next_req, alive = 0, 0, True
    while alive or next_req < n_requests:
        while next_req < n_requests and arrivals[next_req] <= steps:
            paged.submit(prompts[next_req])
            next_req += 1
        alive = paged.step()
        steps += 1
        assert steps < 3000, "soak did not converge"
    assert steps >= 200
    assert audit_programs(paged) == []  # GC003/GC006 hold under tracing

    # terminal timing: the device-faulted request still reports its span
    infos = [paged.request_info(r) for r in range(n_requests)]
    failed = [i for i in infos if i["status"] == "failed"]
    assert failed and failed[0]["error"]
    assert failed[0]["finished_at"] is not None
    assert failed[0]["submitted_at"] > 0

    # latency distributions populated with the full percentile summary
    snap = paged.metrics.snapshot(paged.allocator, paged.index)
    for key in ("ttft_ms", "tpot_ms", "step_latency_ms", "queue_depth"):
        assert snap[key]["count"] > 0
        assert 0 < snap[key]["p50"] <= snap[key]["p90"] <= snap[key]["p99"]

    # Chrome trace export: valid JSON with per-step phase slices, the
    # ProgramRecord-tagged dispatches, request spans, and the instants
    path = paged.export_trace(str(tmp_path / "soak_trace.json"))
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    names = {e["name"] for e in evs}
    dispatches = [e for e in evs if e["name"] == "dispatch"]
    assert dispatches and all(e["ph"] == "X" for e in dispatches)
    assert all("dur" in e and "ts" in e for e in dispatches)
    labels = {e["args"]["program"] for e in dispatches}
    assert any("pdecode" in lb for lb in labels), labels
    assert any(e["args"].get("mode") == "verify" for e in dispatches)
    assert "prefill_chunk" in names
    assert any(e["name"] == "fault" and e["ph"] == "i" for e in evs)
    assert any(e["name"] == "degradation" and e["ph"] == "i" for e in evs)
    req_slices = {e["name"] for e in evs
                  if e.get("pid") == 1 and e["ph"] == "X"}
    assert {"queued", "active"} <= req_slices
    assert any(e.get("pid") == 1 and e["ph"] == "i"
               and e["name"] in ("finished", "failed") for e in evs)

    # jsonl export round-trips the same event stream
    jl = paged.export_trace(str(tmp_path / "soak_trace.jsonl"), fmt="jsonl")
    with open(jl) as f:
        assert len([json.loads(ln) for ln in f]) == len(evs)


def test_trace_events_tag_padded_bucket(params):
    """Every dispatch slice names the kv rung it padded into (and the pad
    waste), every prefill slice its token bucket — the flight-recorder
    view of the catalog ladder (docs/serving.md 'Compiled-program
    catalog'), so an out-of-ladder shape is visible in the trace too."""
    gen = GenerationConfig(max_new_tokens=6)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=64, trace_enabled=True,
                    trace_buffer_steps=64, prefill_chunk_tokens=6),
        TINY_KERNEL,
    )
    for p in _prompts(np.random.default_rng(5), (4, 9)):
        paged.submit(p)
    paged.run_to_completion()
    evs = paged.tracer.chrome_events()
    dispatches = [e for e in evs if e["name"] == "dispatch"]
    assert dispatches
    for e in dispatches:
        bucket, pad = e["args"]["kv_bucket"], e["args"]["kv_pad"]
        assert bucket in paged._kv_buckets
        assert 0 <= pad < bucket
    prefills = [e for e in evs if e["name"] in ("prefill", "prefill_chunk")]
    assert {e["name"] for e in prefills} == {"prefill", "prefill_chunk"}
    for e in prefills:
        bucket, pad = e["args"]["bucket"], e["args"]["pad"]
        assert bucket in paged._prefill_buckets
        assert 0 <= pad < bucket
