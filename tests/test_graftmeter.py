"""graftmeter: device-cost ledger, pad-waste/MFU accounting, SLO burn.

Four layers under test (docs/serving.md "Cost accounting & SLOs"):

- the shared FLOP estimator (flops.py) both the training sweep and the
  serving CostProfiles call — drift between the two formulas is the bug
  the factoring removed;
- the harvest: after ``prewarm()`` every catalog key carries a
  :class:`CostProfile` with nonzero FLOPs/HBM figures, the HBM ledger
  adds up, and ``snapshot()``/``prometheus()`` expose pad-waste per
  rung, the MFU estimate, and headroom;
- **zero interference**: cost accounting on vs off is token-identical
  with identical program registries and h2d upload counts, the meter
  keeps the zero-upload steady state, and per-step overhead stays
  within the tracing bound;
- SLO burn-rate alerts: ``Histogram.count_over`` math, burn windows,
  and the deterministic synthetic-burn drive that climbs the PR 8
  degradation ladder and recovers when the budget refills.
"""

import math

import jax
import numpy as np
import pytest

from neuronx_distributed_llama3_2_tpu.flops import (
    decode_flops_per_token,
    model_flops_per_token,
    train_flops_per_token,
)
from neuronx_distributed_llama3_2_tpu.inference import (
    GenerationConfig,
    InferenceEngine,
)
from neuronx_distributed_llama3_2_tpu.models.llama import (
    LLAMA_CONFIGS,
    LlamaForCausalLM,
)
from neuronx_distributed_llama3_2_tpu.serving import (
    Histogram,
    PagedConfig,
    PagedServingEngine,
    SLOMonitor,
    SLOPolicy,
)
from neuronx_distributed_llama3_2_tpu.serving.accounting import (
    COMPUTE_KINDS,
    MOVE_KINDS,
    EngineDims,
    analytic_profiles,
    cost_table_lines,
    hbm_ledger,
)
from neuronx_distributed_llama3_2_tpu.serving.metrics import ServingMetrics

from tests.test_paged_serving import _prompts

TINY = LLAMA_CONFIGS["tiny"]


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(TINY).init(jax.random.key(0))


def _paged(params, gen, paged_cfg, model_cfg=TINY):
    eng = InferenceEngine(
        model_cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16, 32]
    )
    return PagedServingEngine(eng, gen, paged_cfg)


# ---------------------------------------------------------------------------
# shared FLOP estimator (flops.py)
# ---------------------------------------------------------------------------


def test_flops_formulas_agree_across_consumers():
    # the training formula is exactly 3x the forward formula: the old
    # trainer/metrics.py 6N + 12LHS and the serving 2N + 4LHK unify
    n, layers, hidden, ctx = 1_000_000, 4, 256, 512
    fwd = model_flops_per_token(n, layers, hidden, ctx)
    assert fwd == 2 * n + 4 * layers * hidden * ctx
    assert train_flops_per_token(n, layers, hidden, ctx) == 3.0 * fwd
    assert decode_flops_per_token(n, layers, hidden, ctx) == fwd


def test_trainer_metrics_reexports_shared_helpers():
    from neuronx_distributed_llama3_2_tpu.trainer import metrics as tm

    assert tm.train_flops_per_token is train_flops_per_token


# ---------------------------------------------------------------------------
# Histogram.count_over (the SLO burn primitive)
# ---------------------------------------------------------------------------


def test_count_over_bounds_and_monotonicity():
    h = Histogram(1.0, 64.0, 2.0)
    for v in (0.5, 3.0, 10.0, 40.0, 100.0):
        h.observe(v)
    assert h.count_over(0.0) == h.count
    assert h.count_over(h.max) == 0.0
    prev = h.count
    for t in (0.5, 1.0, 2.0, 8.0, 32.0, 64.0, 99.0):
        cur = h.count_over(t)
        assert 0.0 <= cur <= prev + 1e-9
        prev = cur


def test_count_over_interpolates_within_bucket():
    h = Histogram(1.0, 64.0, 2.0)
    for _ in range(10):
        h.observe(3.0)  # all land in the (2, 4] bucket
    # halfway through the straddled bucket -> half the bucket's count
    assert h.count_over(3.0) == pytest.approx(5.0)
    assert h.count_over(2.0) == pytest.approx(10.0)
    assert h.count_over(4.0) == pytest.approx(0.0)


def test_count_over_empty_histogram():
    assert Histogram().count_over(1.0) == 0.0


# ---------------------------------------------------------------------------
# SLO policy / burn windows (no engine)
# ---------------------------------------------------------------------------


def test_slo_policy_inactive_without_targets():
    assert not SLOPolicy().active
    assert SLOPolicy(tpot_p99_ms=5.0).active
    assert SLOPolicy(ttft_p99_ms=100.0).budget == pytest.approx(0.01)


def test_slo_monitor_alerts_on_sustained_burn_only():
    m = ServingMetrics()
    mon = SLOMonitor(
        SLOPolicy(tpot_p99_ms=1.0, eval_steps=1, window_evals=2), m
    )
    # eval 1: every observation over target, but the window is not full
    for _ in range(50):
        m.hist_tpot_ms.observe(10.0)
    assert mon.on_step(1) is False
    assert m.slo_alerts == 0
    assert m.slo_burn_tpot > 1.0
    # eval 2: window full, still burning -> alert
    for _ in range(50):
        m.hist_tpot_ms.observe(10.0)
    assert mon.on_step(2) is True
    assert m.slo_alerts == 1
    # eval 3: no new observations, but the window still holds misses —
    # the burn lingers (count-weighted over the window) and re-alerts
    assert mon.on_step(3) is True
    # eval 4: the window has fully drained -> zero burn, no alert
    assert mon.on_step(4) is False
    assert m.slo_burn_tpot == 0.0
    assert m.slo_alerts == 2


def test_slo_monitor_respects_eval_cadence():
    m = ServingMetrics()
    mon = SLOMonitor(
        SLOPolicy(tpot_p99_ms=1.0, eval_steps=8, window_evals=1), m
    )
    for _ in range(10):
        m.hist_tpot_ms.observe(10.0)
    assert mon.on_step(7) is False      # off-cadence: not evaluated
    assert m.slo_burn_tpot == 0.0
    assert mon.on_step(8) is True


# ---------------------------------------------------------------------------
# cost-profile harvest + HBM ledger after prewarm
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def prewarmed(params):
    paged = _paged(
        params, GenerationConfig(max_new_tokens=6),
        PagedConfig(
            block_size=8, num_blocks=32, prewarm=True,
            kv_buckets=(8, 16), prefill_buckets=(8, 16),
        ),
    )
    for p in _prompts(np.random.default_rng(0), (5, 11)):
        paged.submit(p)
    paged.run_to_completion()
    return paged


def test_prewarm_profiles_every_catalog_key(prewarmed):
    profiles = prewarmed.cost_profiles
    assert profiles is not None
    for key in prewarmed.catalog.prewarm_keys():
        assert key in profiles, key
    for key, prof in profiles.items():
        assert prof.flops > 0, key
        assert prof.bytes_accessed > 0, key
        assert prof.argument_bytes > 0, key
        assert prof.kind in COMPUTE_KINDS | MOVE_KINDS
        if prof.kind in MOVE_KINDS:
            # move programs keep a nonzero work figure whether XLA
            # reported one or the analytic elements-moved seed stood
            assert prof.flops_source in ("analytic-move", "xla")
    # the dispatch meter folds compute programs only: move-kind "flops"
    # are elements moved and must never pollute MFU
    assert set(prewarmed._flops_by_key) == {
        k for k, p in profiles.items() if p.kind in COMPUTE_KINDS
    }


def test_hbm_ledger_adds_up(prewarmed):
    led = prewarmed.hbm
    assert led is not None
    assert led.footprint_bytes == (
        led.param_bytes + led.pool_bytes + led.resident_bytes
        + led.workspace_bytes
    )
    assert led.headroom_bytes == led.budget_bytes - led.footprint_bytes
    assert led.pool_bytes == prewarmed.metrics.pool_bytes_per_rank
    m = prewarmed.metrics
    assert m.cost_profiled_programs == len(prewarmed.cost_profiles)
    assert m.hbm_footprint_bytes == led.footprint_bytes
    assert m.hbm_headroom_bytes == led.headroom_bytes


def test_hbm_budget_override(params):
    budget = 1 << 28
    paged = _paged(
        params, GenerationConfig(max_new_tokens=4),
        PagedConfig(
            block_size=8, num_blocks=16, prewarm=True,
            kv_buckets=(8,), prefill_buckets=(8,),
            hbm_budget_bytes=budget,
        ),
    )
    assert paged.hbm.budget_bytes == budget
    assert paged.metrics.hbm_headroom_bytes == budget - paged.hbm.footprint_bytes


def test_snapshot_and_prometheus_expose_meter(prewarmed):
    snap = prewarmed.metrics.snapshot(prewarmed.allocator, prewarmed.index)
    assert snap["cost_profiled_programs"] > 0
    assert snap["hbm_headroom_bytes"] > 0
    assert 0.0 <= snap["pad_waste_frac"] <= 1.0
    assert snap["achieved_flops_per_s"] > 0
    assert snap["mfu_est"] >= 0.0
    assert snap["decode_pad_by_rung"], "decode dispatches must tag a rung"
    for rung, rec in snap["decode_pad_by_rung"].items():
        assert rec["need_tokens"] + rec["pad_tokens"] == rung * rec["dispatches"]
        assert 0.0 <= rec["pad_frac"] < 1.0
    assert snap["mfu_by_rung"], "prewarmed decode rungs must carry rooflines"
    for rec in snap["mfu_by_rung"].values():
        assert 0.0 < rec["roofline_mfu"] <= 1.0
    prom = prewarmed.metrics.prometheus()
    assert "serving_decode_pad_tokens_rung{rung=" in prom
    assert "serving_prefill_pad_tokens_rung{rung=" in prom
    assert "serving_roofline_mfu_rung{rung=" in prom
    assert "serving_hbm_headroom_bytes" in prom
    assert "serving_dispatched_flops" in prom


def test_analytic_table_is_deterministic(params):
    def lines():
        paged = _paged(
            params, GenerationConfig(max_new_tokens=4),
            PagedConfig(block_size=8, num_blocks=16,
                        kv_buckets=(8,), prefill_buckets=(8,)),
        )
        return cost_table_lines(analytic_profiles(paged))

    a, b = lines(), lines()
    assert a and a == b  # pure arithmetic: no dispatches, no compiles


def test_engine_dims_and_analytic_cost_scale(params):
    paged = _paged(
        params, GenerationConfig(max_new_tokens=4),
        PagedConfig(block_size=8, num_blocks=16),
    )
    dims = EngineDims.from_engine(paged)
    assert dims.num_params > 0 and dims.num_layers == TINY.num_layers
    from neuronx_distributed_llama3_2_tpu.serving.accounting import (
        analytic_cost,
    )

    f8, b8, _ = analytic_cost(("pdecode", None, 8, False, False), dims)
    f64, b64, _ = analytic_cost(("pdecode", None, 64, False, False), dims)
    assert f64 > f8 and b64 > b8  # longer attention extent costs more


# ---------------------------------------------------------------------------
# zero interference
# ---------------------------------------------------------------------------


def test_cost_accounting_changes_no_tokens_uploads_or_programs(params):
    gen = GenerationConfig(max_new_tokens=10)
    prompts = _prompts(np.random.default_rng(3), (5, 9, 13))

    def run(accounting):
        paged = _paged(
            params, gen,
            PagedConfig(
                block_size=8, num_blocks=32, prewarm=True,
                kv_buckets=(8, 16), prefill_buckets=(8, 16),
                cost_accounting=accounting,
            ),
        )
        for p in prompts:
            paged.submit(p)
        out = paged.run_to_completion()
        m = paged.metrics
        return out, (m.h2d_uploads, m.lane_syncs, m.table_deltas), \
            sorted(map(str, paged._programs))

    out_on, counts_on, progs_on = run(True)
    out_off, counts_off, progs_off = run(False)
    assert out_on == out_off
    assert counts_on == counts_off
    assert progs_on == progs_off


def test_meter_keeps_zero_upload_steady_state(params):
    gen = GenerationConfig(max_new_tokens=24)
    paged = _paged(
        params, gen,
        PagedConfig(block_size=32, num_blocks=8,
                    slo_tpot_p99_ms=60_000.0, slo_eval_steps=4),
    )
    paged.ensure_cost_profiles()
    paged.submit(_prompts(np.random.default_rng(0), (4,))[0])
    paged.step()
    paged.step()
    m = paged.metrics
    for _ in range(12):
        before = (m.h2d_uploads, m.lane_syncs, m.table_deltas)
        assert paged.step()
        assert (m.h2d_uploads, m.lane_syncs, m.table_deltas) == before
    paged.run_to_completion()
    assert m.decode_need_tokens > 0  # the meter did fold while resident


# tier-1 budget: a wall-clock comparison needs repeated runs to beat
# 1-cpu-host noise; the ≤5% contract rides the slow tier (the on/off
# parity tests above stay in-tier)
@pytest.mark.slow
def test_meter_overhead_smoke(params):
    """Per-step host scheduling with the meter + cost profiles + SLO
    monitor armed stays within 5% (+0.2 ms absolute slack against CPU
    jitter) of a bare engine — min-of-3 on warm engines (the
    test_tracing_overhead_smoke bound)."""
    gen = GenerationConfig(max_new_tokens=12)
    prompts = _prompts(np.random.default_rng(4), (6, 9))

    def per_step_ms(metered):
        paged = _paged(
            params, gen,
            PagedConfig(
                block_size=8, num_blocks=32,
                cost_accounting=metered,
                slo_tpot_p99_ms=60_000.0 if metered else None,
            ),
        )
        if metered:
            paged.ensure_cost_profiles()
        best = math.inf
        for _ in range(3):
            h0 = paged.metrics.host_schedule_ms
            s0 = paged.metrics.decode_steps
            for p in prompts:
                paged.submit(p)
            paged.run_to_completion()
            d_host = paged.metrics.host_schedule_ms - h0
            d_steps = paged.metrics.decode_steps - s0
            best = min(best, d_host / max(d_steps, 1))
        return best

    off = per_step_ms(False)
    on = per_step_ms(True)
    assert on <= off * 1.05 + 0.2, (on, off)


def test_pad_counters_consistent_with_rung_breakdown(params):
    paged = _paged(
        params, GenerationConfig(max_new_tokens=8),
        PagedConfig(block_size=8, num_blocks=32),
    )
    for p in _prompts(np.random.default_rng(5), (3, 7, 12)):
        paged.submit(p)
    paged.run_to_completion()
    m = paged.metrics
    assert m.decode_pad_tokens == sum(
        v["pad_tokens"] for v in m.decode_pad_by_rung.values())
    assert m.decode_need_tokens == sum(
        v["need_tokens"] for v in m.decode_pad_by_rung.values())
    assert m.prefill_pad_tokens == sum(
        v["pad_tokens"] for v in m.prefill_pad_by_rung.values())
    assert m.prefill_need_tokens == sum(
        v["need_tokens"] for v in m.prefill_pad_by_rung.values())
    assert 0.0 <= m.pad_waste_frac() <= 1.0


# ---------------------------------------------------------------------------
# SLO burn -> degradation ladder -> recovery (deterministic synthetic drive)
# ---------------------------------------------------------------------------


def test_slo_burn_climbs_ladder_and_recovers(params):
    gen = GenerationConfig(max_new_tokens=48)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=8, num_blocks=64, trace_enabled=True,
            slo_tpot_p99_ms=1.0, slo_eval_steps=2, slo_burn_window=2,
            slo_degrade=True,
            degrade_after_faults=1, degrade_window_steps=16,
            degrade_recover_steps=4,
        ),
    )
    paged.submit(_prompts(np.random.default_rng(6), (5,))[0])
    levels, burning = [], True
    while paged.step():
        if burning:
            # synthetic sustained burn: every "observation" misses the
            # 1 ms TPOT target by 50x
            paged.metrics.hist_tpot_ms.observe(50.0)
        levels.append(paged._degrade_level)
        if burning and paged._degrade_level >= 1:
            burning = False  # budget refill: stop missing the target
        assert len(levels) < 500
    assert max(levels) >= 1, "sustained burn must climb the ladder"
    assert paged.metrics.slo_alerts >= 1
    assert paged.metrics.degradations >= 1
    # clean steps after the burn stopped recovered every rung (the step
    # that retires the request observes its own TPOT, a real miss of the
    # 1 ms target on a CPU, and may start a new climb on the way out)
    assert 0 in levels[levels.index(max(levels)):]
    assert paged.metrics.degradation_level == paged._degrade_level
    # the alert instants made it into the flight recorder
    assert any(
        e["name"] == "slo_burn" for e in paged.tracer.chrome_events()
    )


def test_slo_alert_without_degrade_leaves_ladder_alone(params):
    gen = GenerationConfig(max_new_tokens=16)
    paged = _paged(
        params, gen,
        PagedConfig(
            block_size=8, num_blocks=64,
            slo_tpot_p99_ms=1.0, slo_eval_steps=2, slo_burn_window=2,
            # slo_degrade left False: alerts count, the ladder never moves
            degrade_after_faults=1, degrade_window_steps=16,
            degrade_recover_steps=4,
        ),
    )
    paged.submit(_prompts(np.random.default_rng(7), (5,))[0])
    while paged.step():
        paged.metrics.hist_tpot_ms.observe(50.0)
    assert paged.metrics.slo_alerts >= 1
    assert paged.metrics.degradations == 0
    assert paged._degrade_level == 0
