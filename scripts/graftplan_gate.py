#!/usr/bin/env python
"""graftplan CI gate: synthesize, certify, and load a policy table.

Usage:
    python scripts/graftplan_gate.py                 # full gate
    python scripts/graftplan_gate.py --rules         # GC011 + search space
    python scripts/graftplan_gate.py --list-rules    # alias of --rules
    python scripts/graftplan_gate.py --write-table   # refresh the golden
    python scripts/graftplan_gate.py --table-diff    # built table vs golden

Where graftsched_gate.py model-checks *schedules*, this gate closes the
loop on ROADMAP item 7: it records a mixed-class workload trace on a tiny
CPU-hosted paged engine under FIFO, exports it through
``engine.export_workload()``, and drives the offline synthesis pipeline
(analysis/graftplan.py) end to end:

  1. **Simulate + search** — replay the trace through the deterministic
     step-level simulator and autotune a ``PolicyVector`` (seeded random +
     coordinate descent); the winning vector must beat FIFO on the
     simulated objective (makespan x SLO-burn weighting).
  2. **Certify** — replay the candidate ``TablePolicy`` live through the
     graftsched explorer harness (per-action automaton / invariant-audit /
     leak checks against a FIFO baseline of the same engine) and stamp
     the GC010-clean certificate into the artifact.
  3. **Load under GC011** — the stamped table must load cleanly through
     ``SloPolicy.from_table`` and the engine's ladder-checked loader, and
     a live CPU replay under the loaded policy must be GC010/audit/leak
     clean with every request finishing and token streams identical to
     FIFO.
  4. **Tamper** — a table with a missing certificate, a stale automaton
     fingerprint, and an out-of-ladder chunk budget must each produce a
     GC011 finding (and ``load_policy_table`` must raise), while the
     untampered table and a benign annotation stay quiet.

The synthesized artifact is golden-pinned like the graftcheck catalog and
cost tables: the built table must equal ``scripts/graftplan_table.json``
byte-for-byte, so a policy drift (search change, cost-model change,
automaton change) is a reviewed diff — run ``--write-table`` and commit
the refreshed golden with a rationale. ``--table-diff`` prints the
per-key differences without gating.

The tier-1 suite runs this gate in-process as
``tests/test_graftplan.py::test_gate_in_process`` (sharing the suite's
compile cache) — no separate CI plumbing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

GOLDEN_TABLE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "graftplan_table.json"
)


def _configure_jax() -> None:
    """Script-entry jax setup (CPU host, persistent compile cache).
    NOT called on the in-process tier-1 path — the test suite has already
    configured its backend and cache."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("NXDT_KERNEL_MODE", "reference")
    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache,
    )

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


#: The recorded workload: three long ``batch`` prompts submitted FIRST
#: (chunk-walked prefills whose TTFT busts the objective under any
#: order) and three short ``interactive`` prompts stuck behind them.
#: Under FIFO the interactive class burns its TTFT budget waiting for
#: the batch lanes to drain; a class-weighted vector admits it first and
#: meets the objective — the improvement the gate asserts is real
#: schedule quality, not noise. Tenants alternate so the stride
#: round-robin inside a tier has work to do.
_WORKLOAD = (
    # (prompt_len, service_class, tenant)
    (12, "batch", "acme"),
    (11, "batch", "globex"),
    (10, "batch", "acme"),
    (3, "interactive", "globex"),
    (2, "interactive", "acme"),
    (3, "interactive", "globex"),
)

#: Simulated-milliseconds TTFT objective: first-wave whole prefills land
#: well under it, chunk-walked or queue-delayed admissions land over it.
_TTFT_P99_MS = 0.5

_STATE = None


def make_engine_factory():
    """engine_factory(policy) for the certification harness and the live
    replay legs: a fresh tiny async CPU engine with the mixed-class
    workload already submitted (policy None = FIFO baseline). Prefix
    caching is off so the recorded trace matches the simulator's
    cache-free admission model."""
    global _STATE
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

    if _STATE is None:
        import jax

        cfg = LLAMA_CONFIGS["tiny"]
        params = LlamaForCausalLM(cfg).init(jax.random.key(0))
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
            for n, _, _ in _WORKLOAD
        ]
        _STATE = (cfg, params, prompts)
    cfg, params, prompts = _STATE

    def factory(policy):
        eng = PagedServingEngine(
            InferenceEngine(
                cfg, params, max_batch=3, max_seq_len=32, buckets=[8, 16]
            ),
            GenerationConfig(max_new_tokens=4),
            PagedConfig(
                block_size=4, num_blocks=32, prefill_chunk_tokens=4,
                enable_prefix_caching=False,
                trace_buffer_steps=256, slo_ttft_p99_ms=_TTFT_P99_MS,
            ),
            policy=policy,
        )
        for p, (_, sc, tenant) in zip(prompts, _WORKLOAD):
            eng.submit(p, service_class=sc, tenant=tenant)
        return eng

    return factory


def build_certified_table(seed: int = 0):
    """The synthesis pipeline the gate (and the golden refresh) runs:
    record a FIFO trace live, export the workload, search, build, and
    certify. Returns (table, synth, workload)."""
    from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
        build_table,
        certify_table,
        synthesize,
    )

    factory = make_engine_factory()
    eng = factory(None)
    steps = 0
    while eng.step():
        steps += 1
        if steps > 400:
            raise RuntimeError("recording run did not drain in 400 steps")
    workload = eng.export_workload()
    # host_schedule_ms is wall-clock noise; drop it so the artifact (and
    # its table_id) is deterministic for the golden comparison
    workload.trace = {
        k: workload.trace[k] for k in ("steps", "actions")
        if k in workload.trace
    }
    synth = synthesize(workload, seed=seed)
    table = build_table(workload, synth)
    table = certify_table(table, factory)
    return table, synth, workload


def print_rules() -> None:
    from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import (
        GC_RULES,
    )
    from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
        BURN_STATES,
        automaton_fingerprint,
    )

    print(f"GC011  {GC_RULES['GC011']}")
    print()
    print("search space (PolicyVector coordinates):")
    print("  class_weight    service class -> admission weight "
          "(lower admits earlier)")
    print("  burn_boost      weight subtracted from a class burning its "
          "SLO budget")
    print(f"  prefill_budget  burn state {BURN_STATES} -> prefill-ladder "
          "rung (GC011 rejects off-ladder)")
    print("  verify_cadence  attempt a VERIFY arm every N steps")
    print("  prefer_async    take the async lookahead arm when eligible")
    print()
    print(f"live automaton fingerprint: {automaton_fingerprint()}")


def _diff_tables(built: dict, golden: dict) -> list:
    keys = sorted(set(built) | set(golden))
    out = []
    for k in keys:
        if built.get(k) != golden.get(k):
            out.append(k)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rules", "--list-rules", dest="rules", action="store_true",
        help="print the GC011 rule and the synthesis search space",
    )
    ap.add_argument(
        "--write-table", action="store_true",
        help=f"refresh the golden table artifact ({GOLDEN_TABLE})",
    )
    ap.add_argument(
        "--table-diff", action="store_true",
        help="print per-key diffs between a fresh synthesis and the golden",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rules:
        print_rules()
        return 0

    from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
        PolicyTableError,
        check_policy_table,
        load_policy_table,
    )

    rc = 0
    table, synth, workload = build_certified_table(seed=args.seed)

    if args.write_table:
        with open(GOLDEN_TABLE, "w") as fh:
            json.dump(table, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"graftplan: wrote {GOLDEN_TABLE} (table {table['table_id'][:12]})")
        return 0

    # 1. the search must beat FIFO on the simulated objective
    print(
        f"graftplan: search: fifo objective {synth.fifo.objective:.4f} -> "
        f"table {synth.best.objective:.4f} "
        f"({synth.improvement:+.2%}, {synth.evaluated} vector(s) evaluated)"
    )
    if synth.improvement <= 0:
        print(
            "graftplan: FAIL: synthesized table does not beat FIFO on the "
            "recorded trace"
        )
        rc = 1
    for f in synth.best.findings + synth.fifo.findings:
        print(f.format())
        rc = 1

    # 2. the certificate must be explorer-clean and stream-identical
    cert = table["certificate"]
    if not cert["gc010_clean"]:
        print("graftplan: FAIL: certification run was not GC010-clean:")
        for line in cert["findings"]:
            print(f"  {line}")
        rc = 1
    if not cert["streams_match_fifo"]:
        print(
            "graftplan: FAIL: TablePolicy token streams diverged from the "
            "FIFO baseline during certification"
        )
        rc = 1

    # 3. golden pin: the artifact is a reviewed diff like the graftcheck
    # catalog — any drift must come with a --write-table refresh
    if not os.path.exists(GOLDEN_TABLE):
        print(
            f"graftplan: no golden table at {GOLDEN_TABLE}; run "
            "scripts/graftplan_gate.py --write-table and commit it"
        )
        rc = 1
    else:
        with open(GOLDEN_TABLE) as fh:
            golden = json.load(fh)
        drift = _diff_tables(table, golden)
        if drift:
            print(
                f"graftplan: golden drift in key(s) {drift}; review and "
                "refresh with --write-table"
            )
            if args.table_diff:
                for k in drift:
                    print(f"  built  {k}: "
                          f"{json.dumps(table.get(k), sort_keys=True)[:200]}")
                    print(f"  golden {k}: "
                          f"{json.dumps(golden.get(k), sort_keys=True)[:200]}")
            rc = 1
        else:
            print(
                f"graftplan: golden table fresh "
                f"(table {table['table_id'][:12]})"
            )
    if args.table_diff:
        return rc

    # 4. GC011 load + live replay under the loaded policy
    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        _run_schedule,
    )
    from neuronx_distributed_llama3_2_tpu.serving.scheduler import (
        SloPolicy,
    )

    factory = make_engine_factory()
    try:
        policy = SloPolicy.from_table(table)
    except PolicyTableError as e:
        print(f"graftplan: FAIL: fresh table rejected at load: {e}")
        return 1
    base = _run_schedule(factory, None, "fifo-live", 400)
    live = _run_schedule(factory, policy, "table-live", 400)
    for rep in (base, live):
        for f in rep.findings:
            print(f.format())
            rc = 1
    want = len(_WORKLOAD)
    if len(live.streams) != want:
        print(
            f"graftplan: FAIL: only {len(live.streams)}/{want} requests "
            "finished under the loaded TablePolicy"
        )
        rc = 1
    if live.streams != base.streams:
        print(
            "graftplan: FAIL: live TablePolicy streams diverge from FIFO"
        )
        rc = 1
    else:
        print(
            f"graftplan: live replay: {live.steps} step(s), "
            f"{live.actions} action(s), streams identical to fifo"
        )

    # 5. tampering fixtures: each must produce a GC011 finding and raise
    def tampered(mutate):
        t = json.loads(json.dumps(table))
        mutate(t)
        return t

    fixtures = {
        "missing-certificate": tampered(
            lambda t: t.pop("certificate")
        ),
        "stale-automaton": tampered(
            lambda t: t["fingerprints"].__setitem__(
                "automaton", "0" * 40
            )
        ),
        "out-of-ladder-budget": tampered(
            lambda t: t.__setitem__(
                "prefill_budget",
                {"calm": max(workload.prefill_buckets) + 3},
            )
        ),
    }
    for name, bad in sorted(fixtures.items()):
        findings = check_policy_table(bad)
        raised = False
        try:
            load_policy_table(bad)
        except PolicyTableError:
            raised = True
        if findings and raised:
            print(
                f"graftplan: tamper {name}: caught "
                f"({findings[0].detail})"
            )
        else:
            print(
                f"graftplan: tamper {name}: NOT CAUGHT — GC011 lost the "
                "check this fixture exercises"
            )
            rc = 1

    # quiet fixtures: the untampered table and a benign annotation must
    # load clean (no false positives)
    for name, quiet in (
        ("untampered", json.loads(json.dumps(table))),
        ("benign-annotation", dict(
            json.loads(json.dumps(table)), notes="reviewed 2026-08"
        )),
    ):
        findings = check_policy_table(quiet)
        if findings:
            print(f"graftplan: quiet fixture {name}: FALSE POSITIVE:")
            for f in findings:
                print(f.format())
            rc = 1

    if rc == 0:
        print(
            "graftplan: clean "
            f"(improvement {synth.improvement:+.2%}, certificate fresh, "
            f"{len(fixtures)} tamper(s) caught)"
        )
    return rc


if __name__ == "__main__":
    _configure_jax()
    sys.exit(main())
