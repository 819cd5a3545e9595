"""Fault tolerance for the paged serving engine: chaos injection, failure
domains, invariant audit, degradation ladder, stall watchdog.

The contract under test (docs/serving.md "Failure handling & degradation"):
a fault — injected device error, NaN logits, drafter bug, transient alloc
failure, transfer latency — aborts only the affected request(s). Every
other lane's greedy output stays **token-identical** to a fault-free run
of the same workload (per-lane attention independence), the block pool
drains clean, and the invariant auditor finds nothing. Faulted requests
surface terminally as ``status == "failed"`` with the error detail, and
their partial output is a prefix of the fault-free output (greedy
determinism: every token committed before the fault was a valid token).

The chaos soak at the bottom is the acceptance check: a seeded randomized
arrival schedule with every feature on (async lookahead, speculation,
chunked prefill, tight pool) and every fault class firing, driven twice
to prove bit-reproducibility of the chaos run itself.
"""

import dataclasses

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import audit_programs
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    AllocatorError,
    BlockAllocator,
    EngineStalledError,
    FaultInjector,
    FaultPlan,
    InvariantViolation,
    PagedConfig,
    PagedServingEngine,
    audit_engine,
    make_serving_engine,
)

from tests.drained_policy import LOOPS, loop_policy
from tests.test_paged_serving import _prompts

TINY = LLAMA_CONFIGS["tiny"]
TINY_KERNEL = dataclasses.replace(TINY, use_paged_kernel=True)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


# InferenceEngine is read-only under the paged engine (all serving state —
# pool, tables, programs — lives on PagedServingEngine), so tests share one
# instance per model config; lazy compile keeps each test paying only for
# the program variants it actually dispatches
_ENGINES = {}


def _paged(params, gen, paged_cfg, model_cfg=TINY, injector=None,
           drafter=None, policy=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("buckets", [8, 16, 32])
    key = (id(model_cfg), kw["max_batch"], kw["max_seq_len"],
           tuple(kw["buckets"]))
    if key not in _ENGINES:
        _ENGINES[key] = InferenceEngine(model_cfg, params, **kw)
    return PagedServingEngine(
        _ENGINES[key], gen, paged_cfg,
        injector=injector, drafter=drafter, policy=policy,
    )


def _run(paged, prompts):
    for p in prompts:
        paged.submit(p)
    return paged.run_to_completion()


# shared workloads + configs: the per-fault-class tests all compare against
# a fault-free reference run, so pinning (config, workload) pairs lets one
# baseline drive serve several fault classes (cached below)
GEN10 = GenerationConfig(max_new_tokens=10)
CFG_PLAIN = PagedConfig(block_size=8, num_blocks=64)
CFG_SPEC = PagedConfig(block_size=8, num_blocks=64, spec_draft_tokens=4)
PLAIN_PROMPTS = _prompts(np.random.default_rng(3), (5, 12, 20, 9))
_rep_rng = np.random.default_rng(6)
# repetitive prompts so speculative configs actually draft/verify
REP_PROMPTS = [
    (_rep_rng.integers(1, 9, size=3).tolist() * 8)[:n] for n in (9, 12, 15)
]

_BASELINES = {}


def _baseline(params, gen, cfg, prompts):
    key = (cfg, tuple(tuple(p) for p in prompts), gen.max_new_tokens)
    if key not in _BASELINES:
        _BASELINES[key] = _run(_paged(params, gen, cfg), prompts)
    return _BASELINES[key]


def _statuses(paged):
    return {rid: paged.request_info(rid)["status"] for rid in paged._requests}


def _assert_clean_pool(paged):
    assert paged._pending is None
    assert paged.allocator.active_blocks == 0
    assert paged.allocator.leak_check() == []
    assert audit_engine(paged) == []
    assert audit_programs(paged) == []


def _assert_survivor_parity(paged, baseline):
    """Finished requests match the fault-free run exactly; failed requests
    carry error detail and a prefix of the fault-free output."""
    n_finished = n_failed = 0
    for rid, req in paged._finished.items():
        info = paged.request_info(rid)
        if info["status"] == "failed":
            n_failed += 1
            assert info["error"]
            assert req.out == baseline[rid][: len(req.out)]
        else:
            n_finished += 1
            assert info["status"] == "finished"
            assert info["error"] is None
            assert req.out == baseline[rid]
    return n_finished, n_failed


# ---------------------------------------------------------------------------
# injector units: determinism, schedules, plan validation
# ---------------------------------------------------------------------------


def test_injector_is_deterministic():
    plan = FaultPlan(seed=5, device_rate=0.3, nan_rate=0.2, alloc_rate=0.1)

    def drive(inj):
        for step in range(30):
            inj.begin_step(step)
            inj.device_fault("decode", [0, 1, 2, 3])
            inj.nan_lanes("decode", [0, 1])
            inj.alloc_fault()
        return list(inj.fired)

    assert drive(FaultInjector(plan)) == drive(FaultInjector(plan))
    assert FaultInjector(plan).total_fired == 0  # nothing until consulted


def test_injector_schedule_fires_exactly_once():
    inj = FaultInjector(FaultPlan(schedule=((3, "device"), (3, "drafter"))))
    assert inj.wants("device") and inj.wants("drafter")
    assert not inj.wants("nan")
    for step in range(10):
        inj.begin_step(step)
        inj.device_fault("decode", [0, 1])
        try:
            inj.drafter_fault()
        except RuntimeError:
            pass
    # each entry fired at the first opportunity at/after its step, once
    assert inj.counts["device"] == 1 and inj.counts["drafter"] == 1
    assert [f[0] for f in inj.fired] == [3, 3]


def test_fault_plan_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan(schedule=((0, "gremlin"),))


def test_make_serving_engine_rejects_injector_without_paged(params):
    eng = InferenceEngine(TINY, params, max_batch=2, max_seq_len=32)
    with pytest.raises(ValueError, match="paged"):
        make_serving_engine(eng, injector=FaultInjector(FaultPlan()))


# ---------------------------------------------------------------------------
# allocator: typed errors + leak detection
# ---------------------------------------------------------------------------


def test_allocator_double_release_is_typed():
    a = BlockAllocator(num_blocks=8, block_size=4)
    b = a.alloc()
    a.release(b)
    with pytest.raises(AllocatorError, match="double release") as ei:
        a.release(b)
    assert ei.value.bid == b and ei.value.op == "release"


def test_allocator_incref_after_free_is_typed():
    a = BlockAllocator(num_blocks=8, block_size=4)
    b = a.alloc()
    a.release(b)
    with pytest.raises(AllocatorError, match="not allocated") as ei:
        a.incref(b)
    assert ei.value.bid == b and ei.value.op == "incref"


def test_allocator_leak_check_flags_corruption():
    a = BlockAllocator(num_blocks=8, block_size=4)
    held = a.alloc()
    assert a.leak_check() == []
    # simulate a leak: a registered block also sitting on the free list
    a._free.append(held)
    assert held in a.leak_check()
    a._free.pop()
    assert a.leak_check() == []
    a.release(held)


def test_allocator_fault_hook_reports_transient_exhaustion():
    a = BlockAllocator(num_blocks=8, block_size=4)
    fires = iter([True, False])
    a.fault_hook = lambda: next(fires)
    assert a.alloc() is None          # injected exhaustion, pool untouched
    assert a.free_blocks == 7
    b = a.alloc()                     # next call succeeds normally
    assert b is not None
    a.release(b)
    assert a.leak_check() == []


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------


def test_stall_watchdog_names_stuck_work(params):
    gen = GenerationConfig(max_new_tokens=4)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=32, stall_step_limit=3),
    )
    paged.submit([1, 2, 3])
    paged._free_lanes.clear()  # wedge: queued work, no lane can ever open
    with pytest.raises(EngineStalledError) as ei:
        for _ in range(10):
            paged.step()
    assert ei.value.limit == 3
    assert ei.value.queued == [0]
    assert "no progress for 3" in str(ei.value)


def test_watchdog_tolerates_slow_but_progressing_steps(params):
    # latency faults on every transfer must not trip the watchdog: slow
    # steps still make progress, and progress is what the watchdog counts
    gen = GenerationConfig(max_new_tokens=6)
    inj = FaultInjector(FaultPlan(latency_rate=1.0, latency_ms=0.1))
    paged = _paged(
        params, gen,
        PagedConfig(block_size=8, num_blocks=32, stall_step_limit=2),
        injector=inj,
    )
    out = _run(paged, _prompts(np.random.default_rng(0), (5, 9)))
    assert len(out) == 2
    assert inj.counts["latency"] > 0
    _assert_clean_pool(paged)


# ---------------------------------------------------------------------------
# failure domains: one lane dies, the rest are untouched
# ---------------------------------------------------------------------------


def test_prefill_fault_fails_only_the_admitting_request(params):
    baseline = _baseline(params, GEN10, CFG_PLAIN, PLAIN_PROMPTS)

    inj = FaultInjector(FaultPlan(schedule=((0, "device"),)))
    paged = _paged(params, GEN10, CFG_PLAIN, injector=inj)
    _run(paged, PLAIN_PROMPTS)
    assert inj.counts["device"] == 1
    assert inj.fired[0][2] == "prefill"  # fired at the admission funnel
    n_finished, n_failed = _assert_survivor_parity(paged, baseline)
    assert (n_finished, n_failed) == (3, 1)
    assert paged.metrics.failed_requests == 1
    _assert_clean_pool(paged)


@pytest.mark.parametrize("loop", LOOPS)
def test_decode_fault_fails_one_lane_others_identical(params, loop):
    cfg = CFG_PLAIN
    baseline = _baseline(params, GEN10, cfg, PLAIN_PROMPTS)

    inj = FaultInjector(FaultPlan(seed=2, schedule=((6, "device"),)))
    paged = _paged(
        params, GEN10, cfg, injector=inj, policy=loop_policy(loop)
    )
    _run(paged, PLAIN_PROMPTS)
    assert inj.counts["device"] == 1
    n_finished, n_failed = _assert_survivor_parity(paged, baseline)
    assert (n_finished, n_failed) == (3, 1)
    assert paged.metrics.faults_injected == 1
    _assert_clean_pool(paged)


@pytest.mark.parametrize("cfg", [CFG_PLAIN, CFG_SPEC], ids=["plain", "spec"])
def test_nan_quarantine_fails_the_poisoned_lane(params, cfg):
    baseline = _baseline(params, GEN10, cfg, REP_PROMPTS)

    inj = FaultInjector(FaultPlan(seed=3, schedule=((5, "nan"),)))
    paged = _paged(params, GEN10, cfg, injector=inj)
    assert paged._check_logits  # nan plan implies checked programs
    _run(paged, REP_PROMPTS)
    assert inj.counts["nan"] == 1
    assert paged.metrics.lane_quarantines == 1
    n_finished, n_failed = _assert_survivor_parity(paged, baseline)
    assert (n_finished, n_failed) == (2, 1)
    failed = [r for r in paged._finished.values() if r.failed]
    assert "non-finite" in failed[0].error
    _assert_clean_pool(paged)


CFG_FUSED = PagedConfig(
    block_size=8, num_blocks=64, prefill_chunk_tokens=6, fused_step=True,
)


@pytest.mark.parametrize("kind", ["device", "nan"])
def test_fused_step_fault_fails_one_lane_others_identical(params, kind):
    """The failure domain of a fused mixed-mode dispatch is still ONE
    lane: even though prefill-chunk, verify, and decode rows ride a
    single pmixed program, a fault at its funnel aborts only the chosen
    victim, and every survivor stays token-identical to the fault-free
    UNFUSED run — failure-domain parity and fused token parity pinned by
    the same assertion."""
    baseline = _baseline(
        params, GEN10, dataclasses.replace(CFG_FUSED, fused_step=False),
        PLAIN_PROMPTS,
    )
    # step 2: rid 2 (len 20, chunk 6) is still mid-chunk-walk, so the
    # first device/nan opportunity at or after it is the mixed funnel
    inj = FaultInjector(FaultPlan(seed=4, schedule=((2, kind),)))
    paged = _paged(params, GEN10, CFG_FUSED, injector=inj)
    _run(paged, PLAIN_PROMPTS)
    assert inj.counts[kind] == 1
    assert inj.fired[0][2] == "mixed"  # fired at the fused dispatch
    assert paged.metrics.mixed_dispatches > 0
    if kind == "nan":
        assert paged._check_logits  # nan plan implies checked pmixed
        assert paged.metrics.lane_quarantines == 1
    n_finished, n_failed = _assert_survivor_parity(paged, baseline)
    assert (n_finished, n_failed) == (3, 1)
    assert paged.metrics.failed_requests == 1
    _assert_clean_pool(paged)


def test_detect_nonfinite_clean_run_changes_nothing(params):
    # checked programs with healthy logits: finite everywhere, no
    # quarantines, outputs identical to the unchecked engine
    baseline = _baseline(params, GEN10, CFG_PLAIN, PLAIN_PROMPTS)
    paged = _paged(
        params, GEN10, dataclasses.replace(CFG_PLAIN, detect_nonfinite=True)
    )
    assert paged._check_logits
    assert _run(paged, PLAIN_PROMPTS) == baseline
    assert paged.metrics.lane_quarantines == 0
    _assert_clean_pool(paged)


def test_drafter_fault_is_absorbed_without_failing_requests(params):
    baseline = _baseline(params, GEN10, CFG_SPEC, REP_PROMPTS)

    inj = FaultInjector(FaultPlan(seed=9, drafter_rate=0.5))
    paged = _paged(params, GEN10, CFG_SPEC, injector=inj)
    assert _run(paged, REP_PROMPTS) == baseline  # drafting is advisory
    assert inj.counts["drafter"] > 0
    assert paged.metrics.drafter_faults == inj.counts["drafter"]
    assert paged.metrics.failed_requests == 0
    _assert_clean_pool(paged)


def test_real_drafter_exception_is_absorbed_too(params):
    # the failure contract covers genuine drafter bugs, not just chaos
    class BuggyDrafter:
        def propose(self, history, max_tokens):
            raise ZeroDivisionError("drafter bug")

    baseline = _baseline(params, GEN10, CFG_SPEC, REP_PROMPTS)
    paged = _paged(params, GEN10, CFG_SPEC, drafter=BuggyDrafter())
    assert _run(paged, REP_PROMPTS) == baseline
    assert paged.metrics.drafter_faults > 0
    assert paged.metrics.failed_requests == 0


def test_alloc_fault_causes_backoff_not_failure(params):
    baseline = _baseline(params, GEN10, CFG_PLAIN, PLAIN_PROMPTS)

    inj = FaultInjector(FaultPlan(seed=12, alloc_rate=0.25))
    paged = _paged(params, GEN10, CFG_PLAIN, injector=inj)
    # transient exhaustion surfaces as the normal no-block path (admission
    # back-off / preempt-requeue); greedy recompute keeps outputs identical
    assert _run(paged, PLAIN_PROMPTS) == baseline
    assert inj.counts["alloc"] > 0
    assert paged.metrics.failed_requests == 0
    _assert_clean_pool(paged)


# ---------------------------------------------------------------------------
# request lifecycle: status + error surfacing
# ---------------------------------------------------------------------------


def test_request_info_status_lifecycle(params):
    gen = GenerationConfig(max_new_tokens=12)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=4, num_blocks=10, decode_reserve_blocks=1,
            prefill_chunk_tokens=4,
        ),
    )
    for p in _prompts(np.random.default_rng(13), (14, 14, 12)):
        paged.submit(p)
    seen = set(_statuses(paged).values())
    assert seen == {"queued"}
    alive, steps = True, 0
    while alive:
        alive = paged.step()
        steps += 1
        seen |= set(_statuses(paged).values())
        assert steps < 500
    # the tight pool + chunked prefill walked every non-failure state
    assert {"queued", "prefilling", "active", "preempted", "finished"} <= seen
    assert set(_statuses(paged).values()) == {"finished"}
    for rid in paged._requests:
        assert paged.request_info(rid)["error"] is None
    _assert_clean_pool(paged)


# ---------------------------------------------------------------------------
# invariant auditor
# ---------------------------------------------------------------------------


def test_auditor_passes_mid_flight_and_detects_corruption(params):
    gen = GenerationConfig(max_new_tokens=16)
    paged = _paged(
        params, gen, PagedConfig(block_size=8, num_blocks=64, audit_interval=2)
    )
    for p in _prompts(np.random.default_rng(14), (5, 12, 9)):
        paged.submit(p)
    for _ in range(4):
        paged.step()
    assert audit_engine(paged) == []       # clean engine, mid-decode
    assert paged.metrics.audit_violations == 0

    req = next(iter(paged._active.values()))
    bid = req.table[0]
    paged.allocator._ref[bid] += 1         # corrupt: phantom reference
    violations = audit_engine(paged)
    assert any(f"block {bid}" in s for s in violations)
    with pytest.raises(InvariantViolation):
        paged._audit(strict=True)
    assert paged.metrics.audit_violations > 0

    paged.allocator._ref[bid] -= 1         # restore and drain clean
    assert audit_engine(paged) == []
    paged.run_to_completion()
    _assert_clean_pool(paged)


def test_periodic_audit_counts_violations_without_raising(params):
    gen = GenerationConfig(max_new_tokens=8)
    paged = _paged(
        params, gen, PagedConfig(block_size=8, num_blocks=64, audit_interval=1)
    )
    paged.submit(_prompts(np.random.default_rng(15), (6,))[0])
    paged.step()
    req = next(iter(paged._active.values()))
    paged.allocator._ref[req.table[0]] += 1
    paged.step()                           # periodic audit: logs + counts
    assert paged.metrics.audit_violations > 0
    paged.allocator._ref[req.table[0]] -= 1
    paged.run_to_completion()


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------


def test_degradation_ladder_climbs_and_recovers(params):
    gen = GenerationConfig(max_new_tokens=24)
    prompts = _prompts(np.random.default_rng(16), (5, 12, 9, 17, 6, 11, 8, 14))
    cfg = PagedConfig(
        block_size=8, num_blocks=64,
        degrade_after_faults=1, degrade_window_steps=16,
        degrade_recover_steps=4,
    )
    baseline = _run(_paged(params, gen, dataclasses.replace(
        cfg, degrade_after_faults=0), TINY_KERNEL), prompts)

    inj = FaultInjector(
        FaultPlan(seed=17, schedule=((4, "device"), (7, "device"), (10, "device")))
    )
    paged = _paged(params, gen, cfg, TINY_KERNEL, injector=inj)
    for p in prompts:
        paged.submit(p)
    levels = []
    while paged.step():
        levels.append(paged._degrade_level)
        assert len(levels) < 1000
    # three events, one rung each: spec shed -> async shed -> kernel shed
    assert max(levels) == 3
    assert paged.metrics.degradations == 3
    # rung 3 actually recompiled onto the gather fallback...
    assert any(k[0] == "pdecode" and k[3] for k in paged._programs)
    # ...and clean windows stepped all the way back down
    assert paged._degrade_level == 0
    assert paged.metrics.degradation_level == 0
    assert not paged._gather_shed()
    n_finished, n_failed = _assert_survivor_parity(paged, baseline)
    assert n_failed == 3 and n_finished == 5
    _assert_clean_pool(paged)


def test_ladder_off_by_default_under_faults(params):
    gen = GenerationConfig(max_new_tokens=8)
    prompts = _prompts(np.random.default_rng(18), (5, 9))
    inj = FaultInjector(FaultPlan(schedule=((3, "device"),)))
    paged = _paged(
        params, gen, PagedConfig(block_size=8, num_blocks=64), injector=inj
    )
    _run(paged, prompts)
    assert paged.metrics.degradations == 0
    assert paged._degrade_level == 0


# ---------------------------------------------------------------------------
# fault-free purity: no injector, no behavior change
# ---------------------------------------------------------------------------


def test_fault_free_engine_builds_no_checked_or_gather_programs(params):
    gen = GenerationConfig(max_new_tokens=8)
    paged = _paged(params, gen, PagedConfig(block_size=8, num_blocks=64))
    _run(paged, _prompts(np.random.default_rng(19), (5, 12)))
    assert paged.injector is None
    assert paged._check_logits is False
    assert paged._zero_mask is None        # the nan-mask cache never built
    for key in paged._programs:
        if key[0] == "pdecode":
            assert key[3] is False and key[4] is False  # gather, checked
        assert key[0] != "pverify"
    m = paged.metrics
    assert m.faults_injected == 0
    assert m.failed_requests == 0
    assert m.lane_quarantines == 0
    assert m.degradation_level == 0
    assert m.audit_violations == 0


# ---------------------------------------------------------------------------
# the chaos soak: everything on, every fault class, reproducible
# ---------------------------------------------------------------------------


def _chaos_soak(params, n_requests, arrival_span, max_new, plan, workload_seed,
                repeat_chaos=False):
    rng = np.random.default_rng(workload_seed)
    gen = GenerationConfig(max_new_tokens=max_new)
    # chaos drives run prewarmed: after the catalog freeze the only legal
    # mid-traffic compiles are the degradation ladder's gather twins
    # (exempt from steadystate_compiles / GC008)
    cfg = PagedConfig(
        block_size=4, num_blocks=24, decode_reserve_blocks=1,
        prefill_chunk_tokens=8, spec_draft_tokens=4,
        stall_step_limit=300, audit_interval=8, audit_debug=True,
        degrade_after_faults=3, degrade_window_steps=32,
        degrade_recover_steps=16, prewarm=True,
    )
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
    arrivals = np.sort(rng.integers(0, arrival_span, size=n_requests)).tolist()

    def drive(injector):
        paged = _paged(
            params, gen,
            cfg if injector is not None
            else dataclasses.replace(
                cfg, audit_interval=0, audit_debug=False, prewarm=False,
            ),
            injector=injector,
        )
        steps, next_req, alive = 0, 0, True
        while alive or next_req < n_requests:
            while next_req < n_requests and arrivals[next_req] <= steps:
                paged.submit(prompts[next_req])
                next_req += 1
            alive = paged.step()
            steps += 1
            assert steps < 5000, "chaos soak did not converge"
        _assert_clean_pool(paged)
        assert len(paged._finished) == n_requests
        return paged

    baseline = drive(None)
    base_out = {rid: r.out for rid, r in baseline._finished.items()}
    chaos = drive(FaultInjector(plan))
    repeat = drive(FaultInjector(plan)) if repeat_chaos else None
    return chaos, base_out, repeat


def _check_soak(chaos, base_out, plan):
    inj = chaos.injector
    for kind in ("device", "nan", "drafter", "alloc", "latency"):
        assert inj.counts[kind] >= 1, f"{kind} fault never fired"
    n_finished, n_failed = _assert_survivor_parity(chaos, base_out)
    assert n_failed >= 1          # device + nan faults kill their victims
    assert n_finished >= 1        # ...and never take the engine with them
    m = chaos.metrics
    assert m.faults_injected == inj.total_fired
    assert m.failed_requests == n_failed
    assert m.audit_violations == 0  # strict audits ran at every transition
    # prewarmed catalog held through the whole chaos run: nothing but
    # ladder-sanctioned gather twins compiled after the freeze
    assert m.prewarm_compiles > 0
    assert m.steadystate_compiles == 0
    # reproducibility: the same plan over the same workload fires the same
    # faults — (workload seed, FaultPlan) fully determines a chaos run
    return [f[:3] for f in inj.fired]


# tier-1 budget: each fault class has its own in-tier test; the
# all-classes chaos soak joins chaos_soak_long in the slow tier
@pytest.mark.slow
def test_chaos_soak_all_fault_classes(params):
    plan = FaultPlan(
        seed=7, drafter_rate=0.05, alloc_rate=0.02, latency_rate=0.05,
        latency_ms=0.1,
        schedule=(
            (5, "device"), (15, "nan"), (20, "drafter"),
            (25, "alloc"), (30, "latency"),
        ),
    )
    chaos, base_out, chaos2 = _chaos_soak(
        params, n_requests=12, arrival_span=50, max_new=10,
        plan=plan, workload_seed=1234, repeat_chaos=True,
    )
    fired = _check_soak(chaos, base_out, plan)
    assert [f[:3] for f in chaos2.injector.fired] == fired
    assert {r: q.out for r, q in chaos2._finished.items()} == {
        r: q.out for r, q in chaos._finished.items()
    }


def test_chaos_soak_script_smoke_mode():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))
    import chaos_soak

    record = chaos_soak.run_bench(chaos_soak.build_args(["--smoke"]))
    assert record.get("gate_failure") is None
    assert record["smoke"] is True
    assert record["failed"] >= 1 and record["finished"] >= 1
    assert all(n >= 1 for n in record["faults_by_kind"].values())
    assert record["audit_violations"] == 0


@pytest.mark.slow
def test_chaos_soak_long(params):
    plan = FaultPlan(
        seed=21, device_rate=0.004, nan_rate=0.004, drafter_rate=0.08,
        alloc_rate=0.03, latency_rate=0.08, latency_ms=0.1,
        schedule=(
            (10, "device"), (40, "nan"), (60, "drafter"),
            (80, "alloc"), (100, "latency"), (120, "device"), (140, "nan"),
        ),
    )
    chaos, base_out, _ = _chaos_soak(
        params, n_requests=30, arrival_span=160, max_new=14,
        plan=plan, workload_seed=4321,
    )
    _check_soak(chaos, base_out, plan)
