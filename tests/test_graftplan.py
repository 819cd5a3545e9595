"""graftplan: policy-table fixtures, GC011 tampering, the synthesis gate
in-process, and simulator-vs-live calibration.

Layered like the other analyzer suites (test_shardlint / test_graftcheck
/ test_graftsched):

- **jax-free unit fixtures** — PolicyVector round-trips and ranking, the
  shared ``rank_queue`` admission kernel, fingerprint stability, and
  GC011 tampering/quiet fixtures on hand-built tables (no engine);
- **the CI gate in-process** — ``scripts/graftplan_gate.py`` records a
  trace, synthesizes, certifies, golden-pins and tamper-checks a policy
  table against a live tiny engine and must exit 0;
- **calibration** — the same seeded workload run live (sync CPU engine)
  and in the simulator must match exactly on step count, admission
  order, per-class token totals and dispatch count, for FIFO and for
  table-driven vectors, and the simulated objective must be monotone in
  the live cost ordering across policy vectors;
- **registry** — ``make_policy`` lists the full three-policy registry in
  its rejection message.
"""

import dataclasses
import importlib.util
import os

import pytest

from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
    GC011,
    PolicyTableError,
    PolicyVector,
    Simulator,
    Workload,
    WorkloadRequest,
    _stamp,
    automaton_fingerprint,
    check_policy_table,
    fifo_vector,
    ladder_fingerprint,
    load_policy_table,
    simulate,
    synthesize,
    trace_fingerprint,
)
from neuronx_distributed_llama3_2_tpu.serving.policy import (
    QueuedRequest,
    make_policy,
)
from neuronx_distributed_llama3_2_tpu.serving.scheduler import (
    SloPolicy,
    TablePolicy,
    rank_queue,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- jax-free fixtures -------------------------------------------------------


def test_policy_vector_roundtrip():
    vec = PolicyVector(
        class_weight={"interactive": 0.0, "batch": 2.0},
        burn_boost=3.0,
        prefill_budget={"calm": 8, "tpot_burn": 4},
        verify_cadence=2,
        prefer_async=False,
    )
    assert PolicyVector.from_dict(vec.to_dict()) == vec


def test_policy_vector_rank():
    vec = PolicyVector(class_weight={"interactive": 0.0, "batch": 1.0})
    assert vec.rank("interactive", False) < vec.rank("batch", False)
    # unknown classes rank behind every listed one
    assert vec.rank("bulk", False) > vec.rank("batch", False)
    # burn boost lifts a burning class
    assert vec.rank("batch", True) < vec.rank("interactive", False)


def test_fifo_vector_is_identity():
    vec = fifo_vector()
    # equal weights, no boost: every class ranks the same -> rank_queue
    # preserves FCFS order exactly
    queued = [
        QueuedRequest(rid=r, service_class=c, tenant=t, tokens=4, position=i)
        for i, (r, c, t) in enumerate([
            (7, "batch", "a"), (3, "interactive", "b"), (9, "batch", "a"),
        ])
    ]
    order = rank_queue(queued, lambda cls: vec.rank(cls, False))
    assert order == [7, 3, 9]
    assert vec.budget_for("calm") is None


def test_rank_queue_tiers_tenants_fcfs():
    queued = [
        QueuedRequest(rid=r, service_class=c, tenant=t, tokens=4, position=i)
        for i, (r, c, t) in enumerate([
            (0, "batch", "acme"), (1, "batch", "acme"),
            (2, "interactive", "acme"), (3, "batch", "globex"),
            (4, "interactive", "globex"),
        ])
    ]
    rank = {"interactive": 0, "batch": 1}
    order = rank_queue(queued, lambda cls: rank[cls])
    # interactive tier first (round-robin acme/globex), then batch
    # (acme holds positions 0,1 -> FCFS within tenant, striped with
    # globex's single request)
    assert order == [2, 4, 0, 3, 1]


def test_rank_queue_tenant_weights_stride():
    # one tier, acme holds 4 requests vs globex's 2: doubling acme's
    # weight buys it back-to-back picks once the strides diverge
    queued = [
        QueuedRequest(rid=i, service_class="batch", tenant=t,
                      tokens=4, position=i)
        for i, t in enumerate(
            ["acme", "acme", "acme", "acme", "globex", "globex"]
        )
    ]
    fair = rank_queue(queued, lambda cls: 0)
    assert fair == [0, 4, 1, 5, 2, 3]
    heavy = rank_queue(
        queued, lambda cls: 0, tenant_weights={"acme": 2.0}
    )
    assert heavy == [0, 4, 1, 2, 5, 3]


def test_fingerprints_stable_and_sensitive():
    assert automaton_fingerprint() == automaton_fingerprint()
    a = ladder_fingerprint([8, 16], [8, 16, 32])
    assert a == ladder_fingerprint([8, 16], [8, 16, 32])
    assert a != ladder_fingerprint([8, 16, 32], [8, 16, 32])
    wd = {"config": {"lanes": 2}, "requests": [{"rid": 0}]}
    assert trace_fingerprint(wd) == trace_fingerprint(dict(wd, trace={"x": 1}))
    assert trace_fingerprint(wd) != trace_fingerprint(
        {"config": {"lanes": 3}, "requests": [{"rid": 0}]}
    )


_PREFILL = [8, 16, 32]
_KV = [8, 16, 32]


def _hand_table() -> dict:
    """A minimal, internally consistent, certificate-bearing table
    (never touched an engine — GC011 checks are pure)."""
    fp = automaton_fingerprint()
    return _stamp({
        "version": 1,
        "generator": "test",
        "ladder": {"prefill": list(_PREFILL), "kv": list(_KV)},
        "fingerprints": {
            "automaton": fp,
            "ladder": ladder_fingerprint(_PREFILL, _KV),
            "trace": "0" * 40,
        },
        "prefill_budget": {"calm": 16, "tpot_burn": 8},
        "vector": PolicyVector().to_dict(),
        "certificate": {
            "automaton_fingerprint": fp,
            "gc010_clean": True,
            "streams_match_fifo": True,
        },
    })


def test_quiet_table_loads_clean():
    table = _hand_table()
    assert check_policy_table(table) == []
    assert check_policy_table(
        table, prefill_buckets=_PREFILL, kv_buckets=_KV
    ) == []
    assert load_policy_table(table)["table_id"] == table["table_id"]


def test_gc011_missing_certificate():
    table = _hand_table()
    del table["certificate"]
    findings = check_policy_table(table)
    assert [f.rule for f in findings] == [GC011]
    assert "certificate" in findings[0].message
    with pytest.raises(PolicyTableError, match="GC011"):
        load_policy_table(table)


def test_gc011_unclean_certificate():
    table = _hand_table()
    table["certificate"]["gc010_clean"] = False
    findings = check_policy_table(table)
    assert len(findings) == 1 and "GC010-unclean" in findings[0].message


def test_gc011_stale_automaton_names_component():
    table = _hand_table()
    table["fingerprints"]["automaton"] = "f" * 40
    table["certificate"]["automaton_fingerprint"] = "f" * 40
    findings = check_policy_table(table)
    assert findings and all(f.rule == GC011 for f in findings)
    assert any("the stale component is the automaton" in f.message
               for f in findings)
    with pytest.raises(PolicyTableError):
        load_policy_table(table)


def test_gc011_stale_ladder_names_component():
    table = _hand_table()
    findings = check_policy_table(
        table, prefill_buckets=[4, 8, 32], kv_buckets=_KV
    )
    msgs = [f.message for f in findings]
    assert any("the stale component is the ladder" in m for m in msgs)
    # and the budget check runs against the LIVE ladder when given one:
    # 16 is a rung of the table's ladder but not of [4, 8, 32]
    assert any("not a rung" in m for m in msgs)


def test_gc011_out_of_ladder_budget():
    table = _hand_table()
    table["prefill_budget"] = {"calm": 13}
    findings = check_policy_table(table)
    assert len(findings) == 1
    assert "out-of-ladder budget calm=13" in findings[0].detail
    with pytest.raises(PolicyTableError):
        load_policy_table(table)


def test_gc011_hand_edited_ladder():
    table = _hand_table()
    table["ladder"]["prefill"] = [4, 8]
    findings = check_policy_table(table)
    assert any("hand-edited" in f.message for f in findings)


def test_from_table_checks_and_builds_table_policy():
    policy = SloPolicy.from_table(_hand_table())
    assert isinstance(policy, TablePolicy)
    assert policy.table_id
    bad = _hand_table()
    del bad["certificate"]
    with pytest.raises(PolicyTableError):
        SloPolicy.from_table(bad)


def test_make_policy_lists_full_registry():
    with pytest.raises(ValueError, match=r"'fifo', 'slo', 'table'"):
        make_policy("round-robin")
    assert isinstance(make_policy("table"), TablePolicy)


# -- pure-simulator workload fixtures ---------------------------------------


def _toy_dims():
    from neuronx_distributed_llama3_2_tpu.serving.accounting import (
        EngineDims,
    )

    return EngineDims(
        num_params=10_000, param_bytes=20_000, num_layers=2,
        hidden_size=16, num_kv_heads=2, head_dim=8, vocab_size=64,
        max_batch=2, table_width=10, block_size=4, num_blocks=32,
        kv_bytes_per_elem=2, scale_bytes=0, tp_size=1,
    )


def _toy_workload(**over) -> Workload:
    base = dict(
        block_size=4, num_blocks=32, decode_reserve_blocks=2, lanes=2,
        max_seq_len=32, prefill_chunk_tokens=4,
        prefill_buckets=(8, 16, 32), kv_buckets=(8, 16, 32),
        dims=_toy_dims(),
        requests=[
            WorkloadRequest(rid=0, prompt_tokens=10, max_new_tokens=3,
                            service_class="batch", tenant="a"),
            WorkloadRequest(rid=1, prompt_tokens=9, max_new_tokens=3,
                            service_class="batch", tenant="b"),
            WorkloadRequest(rid=2, prompt_tokens=2, max_new_tokens=3,
                            service_class="interactive", tenant="a"),
            WorkloadRequest(rid=3, prompt_tokens=3, max_new_tokens=3,
                            service_class="interactive", tenant="b"),
        ],
        slo_ttft_p99_ms=0.5,
    )
    base.update(over)
    return Workload(**base)


def test_workload_roundtrip():
    w = _toy_workload()
    again = Workload.from_dict(w.to_dict())
    assert again.to_dict() == w.to_dict()
    assert again.classes() == ["batch", "interactive"]
    assert trace_fingerprint(again.to_dict()) == trace_fingerprint(
        w.to_dict()
    )


@pytest.mark.parametrize("flag", [False, True])
def test_workload_artifact_from_before_the_lookahead_was_the_engine(flag):
    """An artifact written while the look-ahead was an option carries that
    option in its ``config`` block. It is read without the key — not
    refused as stale: it loads as the workload it records, simulates as
    every engine now steps (whatever the flag said), and writes back
    without it."""
    w = _toy_workload()
    old = w.to_dict()
    key = "async" + "_loop"
    assert key not in old["config"]
    old["config"][key] = flag
    again = Workload.from_dict(old)
    assert again.to_dict() == w.to_dict()
    assert not hasattr(again, key)
    got, want = simulate(again, None), simulate(w, None)
    assert got.findings == [] and got.makespan_ms == want.makespan_ms
    assert got.steps == want.steps


def test_simulator_fifo_drains_clean():
    res = simulate(_toy_workload())
    assert res.findings == []
    assert res.finished == [0, 1, 2, 3]
    assert res.per_class_tokens == {"batch": 6, "interactive": 6}
    assert res.admission_order[:2] == [0, 1]  # FCFS: batch first
    assert res.makespan_ms > 0
    assert res.dispatches > 0


def test_simulator_vector_reorders_admission():
    vec = PolicyVector(class_weight={"interactive": 0.0, "batch": 1.0},
                       burn_boost=0.0)
    res = simulate(_toy_workload(), vec)
    assert res.findings == []
    assert res.admission_order[:2] == [2, 3]  # interactive promoted
    assert sorted(res.finished) == [0, 1, 2, 3]


def test_simulator_budget_serializes_prefill():
    free = simulate(_toy_workload())
    # with 2 lanes and 4-token chunks the aggregate demand is 8/step, so
    # a 4-token budget halves the chunk walk's width
    tight = simulate(_toy_workload(), PolicyVector(
        class_weight={}, burn_boost=0.0,
        prefill_budget={"calm": 4, "ttft_burn": 4, "tpot_burn": 4},
    ))
    # a budget below the aggregate chunk demand stretches the chunk walk
    # over more steps
    assert tight.steps > free.steps
    assert tight.findings == []


def test_simulator_async_overlap_is_cheaper():
    # a decode-heavy workload: the async lookahead costs one extra
    # arming step, so overlap only wins once enough steady-state decode
    # steps amortize it
    long = [
        dataclasses.replace(r, max_new_tokens=24)
        for r in _toy_workload().requests
    ]
    sync = simulate(
        _toy_workload(requests=long), PolicyVector(
            class_weight={}, burn_boost=0.0, prefer_async=False,
        ))
    overlap = simulate(
        _toy_workload(requests=long), PolicyVector(
            class_weight={}, burn_boost=0.0, prefer_async=True,
        ))
    assert overlap.findings == [] and sync.findings == []
    # the lookahead overlaps host scheduling with device compute, so the
    # same token work takes less simulated wall clock
    assert overlap.makespan_ms < sync.makespan_ms


def test_synthesize_beats_or_ties_fifo():
    synth = synthesize(_toy_workload(), seed=0, random_candidates=4)
    assert synth.improvement >= 0
    assert synth.evaluated >= 6
    # deterministic for a fixed (workload, seed)
    again = synthesize(_toy_workload(), seed=0, random_candidates=4)
    assert again.best_vector == synth.best_vector
    assert again.best.objective == synth.best.objective


@pytest.mark.slow
def test_synthesize_multi_seed_stability():
    """Slow tier: the search must beat or tie FIFO from every seed, and
    the simulated objective of the winner must be reproducible."""
    objectives = {}
    for seed in range(4):
        synth = synthesize(_toy_workload(), seed=seed)
        assert synth.improvement >= 0, seed
        objectives[seed] = synth.best.objective
    assert objectives[0] == synthesize(_toy_workload(), seed=0).best.objective


# -- the CI gate in-process --------------------------------------------------


def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "graftplan_gate",
        os.path.join(REPO_ROOT, "scripts", "graftplan_gate.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gate_in_process(capsys):
    """The full gate — record, synthesize (must beat FIFO), certify,
    golden-pin, GC011 load, live replay, tampering fixtures — exits 0."""
    gate = _load_gate()
    assert gate.main([]) == 0
    out = capsys.readouterr().out
    assert "graftplan: clean" in out
    assert "3 tamper(s) caught" in out
    assert "golden table fresh" in out


def test_gate_list_rules(capsys):
    gate = _load_gate()
    assert gate.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "GC011" in out
    assert "prefill_budget" in out


# -- dashboard policy panel --------------------------------------------------


def _load_dashboard():
    spec = importlib.util.spec_from_file_location(
        "serving_dashboard_graftplan",
        os.path.join(REPO_ROOT, "scripts", "serving_dashboard.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dashboard_policy_panel_renders():
    mod = _load_dashboard()
    snap = {
        "policy_table_id": "abc123def456",
        "policy_table_stale": 1,
        "policy_simulated_burn": {"interactive": {"ttft": 0.25, "tpot": 0.0}},
        "slo_burn_by_class": {"interactive": {"ttft": 0.5, "tpot": 0.0}},
    }
    text = mod.render_snapshot(snap)
    assert "policy     table abc123def456" in text
    assert "plan/interactive" in text
    assert "sim   0.250 obs   0.500" in text
    assert "WARNING: stale certificate" in text
    # fresh table: no warning line
    snap["policy_table_stale"] = 0
    assert "WARNING" not in mod.render_snapshot(snap)
    # no table loaded: no panel
    assert "policy     table" not in mod.render_snapshot({})


def test_dashboard_parses_policy_prometheus():
    mod = _load_dashboard()
    prom = (
        'serving_policy_table_info{table_id="abc123def456"} 1\n'
        "serving_policy_table_stale 1\n"
        'serving_policy_simulated_burn_class'
        '{class="interactive",objective="ttft"} 0.25\n'
    )
    snap = mod.parse_prometheus(prom)
    assert snap["policy_table_id"] == "abc123def456"
    assert snap["policy_table_stale"] == 1
    assert snap["policy_simulated_burn"]["interactive"]["ttft"] == 0.25
    assert "WARNING: stale certificate" in mod.render_snapshot(snap)


# -- simulator-vs-live calibration ------------------------------------------

_CAL_WORKLOAD = (
    (12, "batch", "acme"),
    (11, "batch", "globex"),
    (10, "batch", "acme"),
    (3, "interactive", "globex"),
    (2, "interactive", "acme"),
    (3, "interactive", "globex"),
)


def _calibration_factory():
    """Sync loop, prefix caching off, no SLO monitor, ample pool: the
    projection of the engine the simulator models exactly."""
    import jax
    import numpy as np

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
    )

    cfg = LLAMA_CONFIGS["tiny"]
    params = LlamaForCausalLM(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
        for n, _, _ in _CAL_WORKLOAD
    ]

    def factory(policy):
        eng = PagedServingEngine(
            InferenceEngine(
                cfg, params, max_batch=3, max_seq_len=32, buckets=[8, 16]
            ),
            GenerationConfig(max_new_tokens=4),
            PagedConfig(
                block_size=4, num_blocks=64, prefill_chunk_tokens=4,
                enable_prefix_caching=False,
                trace_buffer_steps=256,
            ),
            policy=policy,
        )
        for p, (_, sc, tenant) in zip(prompts, _CAL_WORKLOAD):
            eng.submit(p, service_class=sc, tenant=tenant)
        return eng

    return factory


def _run_live(factory, vector):
    """One live leg: run to drain, return the exact-match observables."""
    if vector is None:
        policy = None
    else:
        policy = TablePolicy()
        policy.apply({"vector": vector.to_dict()})
    eng = factory(policy)
    steps, alive = 0, True
    while alive:
        alive = eng.step()
        steps += 1
        assert steps < 200, "live leg did not drain"
    admitted = sorted(
        (r for r in eng._requests.values() if r.admitted_at is not None),
        key=lambda r: r.admitted_at,
    )
    per_class = {}
    for r in eng._requests.values():
        per_class[r.service_class] = (
            per_class.get(r.service_class, 0) + len(r.out)
        )
    dispatches = sum(
        v["dispatches"] for v in eng.metrics.decode_pad_by_rung.values()
    ) + sum(
        v["dispatches"] for v in eng.metrics.prefill_pad_by_rung.values()
    )
    return {
        "engine": eng,
        "steps": steps,
        "admission_order": [r.rid for r in admitted],
        "per_class": per_class,
        "dispatches": dispatches,
        "host_ms": eng.metrics.host_schedule_ms,
    }


def test_simulator_matches_live_engine():
    """The calibration contract: same seeded workload, live sync CPU
    engine vs simulator — step count, admission order, per-class token
    totals and dispatch count match EXACTLY, for FIFO and for two
    table-driven vectors; and the simulated cost is monotone in the
    live (dispatch count, host_schedule_ms) ordering across vectors."""
    factory = _calibration_factory()
    vectors = {
        "fifo": None,
        "weighted": PolicyVector(
            class_weight={"interactive": 0.0, "batch": 1.0},
            burn_boost=0.0,
        ),
        "budgeted": PolicyVector(
            class_weight={}, burn_boost=0.0,
            prefill_budget={"calm": 8, "ttft_burn": 8, "tpot_burn": 8},
        ),
    }
    live = {name: _run_live(factory, vec) for name, vec in vectors.items()}
    workload = live["fifo"]["engine"].export_workload()
    assert workload.slo_ttft_p99_ms is None  # no monitor: burns stay 0

    sims = {
        name: Simulator(workload, vec).run()
        for name, vec in vectors.items()
    }
    for name in vectors:
        sim, obs = sims[name], live[name]
        assert sim.findings == [], name
        assert sim.steps == obs["steps"], name
        assert sim.admission_order == obs["admission_order"], name
        assert sim.per_class_tokens == obs["per_class"], name
        assert sim.dispatches == obs["dispatches"], name
    # FIFO and the reordering vector do the same work; the budget vector
    # strictly serializes the chunk walk -> more steps, more dispatches
    assert live["budgeted"]["dispatches"] > live["fifo"]["dispatches"]
    # monotone: rank the legs by live cost (dispatch count, then host
    # scheduling time) and require the simulated objective to rank the
    # same way
    by_live = sorted(
        vectors, key=lambda n: (live[n]["dispatches"], live[n]["host_ms"])
    )
    by_sim = sorted(vectors, key=lambda n: sims[n].objective)
    assert by_sim.index("budgeted") == by_live.index("budgeted") == 2
    sim_costs = [sims[n].objective for n in by_live]
    assert sim_costs == sorted(sim_costs)
