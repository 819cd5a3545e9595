#!/usr/bin/env python
"""graftsched CI gate: model-check the serving engine's step schedules.

Usage:
    python scripts/graftsched_gate.py                 # explore + mutations
    python scripts/graftsched_gate.py --rules         # print rule + automaton
    python scripts/graftsched_gate.py --list-rules    # alias of --rules
    python scripts/graftsched_gate.py --schedules 8 --seed 3

Where shardlint_gate.py lints source ASTs and graftcheck_gate.py lints
traced programs, this gate checks *schedules*: it drives a tiny CPU-hosted
paged engine (async lookahead on, chunked prefill, staggered finishes)
through the default FIFO schedule plus a set of seeded permutations of the
commuting action orders, asserting after every executed action that

  - the host-state invariant auditor (serving/invariants.py) is clean,
  - the block pool's partition invariant (leak_check) holds,
  - the schedule legality automaton (analysis/graftsched.py) accepts,

and at the end that every schedule produced token streams identical to
the FIFO baseline. Candidate schedules differing only at statically
independent decision points are pruned without running (sleep sets).

It then replays the recorded baseline trace with two seeded mutations —
block release before the lame-duck drain, and a full-lane resident sync
mid-pipeline, both historical bugs — and requires the automaton to
REJECT both: the model checker's own regression test. Exit status is
nonzero on any violation, stream divergence, or uncaught mutation.

The tier-1 suite runs this gate in-process as
``tests/test_graftsched.py::test_gate_main_in_process`` (sharing the
suite's compile cache) — no separate CI plumbing.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


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


# staggered prompt lengths: straddle the chunk size (whole-prefill and
# chunk-walk admissions) and finish at different steps, so the baseline
# trace contains admission waves, lame-duck drains and FINISH records —
# the mutation sites run_seeded_mutations needs.
_PROMPT_LENS = (3, 6, 9, 4)

_STATE = None


#: (service_class, tenant) per prompt for the mixed-traffic SloPolicy leg:
#: interleaved classes across two tenants, so the admission ranking has
#: real reordering to do (queue depth 4 > 3 lanes).
_MIXED_CLASSES = (
    ("batch", "acme"), ("batch", "globex"),
    ("interactive", "acme"), ("interactive", "globex"),
)


def make_engine_factory(mixed: bool = False):
    """engine_factory(policy) for :func:`analysis.graftsched.explore`:
    a fresh tiny async engine with the workload already submitted
    (policy None = the engine-default FifoPolicy baseline). ``mixed``
    submits the same prompts under the mixed service classes / tenants
    the SloPolicy leg schedules over."""
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
            for n in _PROMPT_LENS
        ]
        _STATE = (cfg, params, prompts)
    cfg, params, prompts = _STATE

    def factory(policy):
        eng = PagedServingEngine(
            InferenceEngine(
                cfg, params, max_batch=3, max_seq_len=32, buckets=[8, 16]
            ),
            GenerationConfig(max_new_tokens=5),
            PagedConfig(
                block_size=8, num_blocks=32, prefill_chunk_tokens=4,
                trace_buffer_steps=128,
            ),
            policy=policy,
        )
        if mixed:
            for p, (sc, tenant) in zip(prompts, _MIXED_CLASSES):
                eng.submit(p, service_class=sc, tenant=tenant)
        else:
            for p in prompts:
                eng.submit(p)
        return eng

    return factory


def print_rules() -> None:
    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        AUTOMATON,
    )
    from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import (
        GC_RULES,
    )

    print(f"GC010  {GC_RULES['GC010']}")
    print()
    print("legality automaton (state: outstanding dispatches, freed lanes):")
    w = max(len(e["action"]) for e in AUTOMATON)
    g = max(len(e["guard"]) for e in AUTOMATON)
    for e in AUTOMATON:
        print(f"  {e['action']:<{w}}  {e['guard']:<{g}}  {e['effect']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rules", "--list-rules", dest="rules", action="store_true",
        help="print the GC010 rule and the legality automaton table",
    )
    ap.add_argument(
        "--schedules", type=int, default=5,
        help="seeded schedules to run beyond the FIFO baseline",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rules:
        print_rules()
        return 0

    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        check_trace,
        explore,
        run_seeded_mutations,
    )

    rc = 0
    factory = make_engine_factory()
    report = explore(
        factory, schedules=args.schedules, seed=args.seed,
    )
    print(f"graftsched: explore: {report.summary()}")
    for rep in [report.baseline, *report.explored]:
        for f in rep.findings:
            print(f.format())
            rc = 1
    for m in report.mismatches:
        print(f"graftsched: STREAM MISMATCH: {m}")
        rc = 1

    # the pure replay path (what check_action_trace runs at teardown):
    # the recorded baseline trace must be accepted end to end
    replay = check_trace(report.baseline.trace)
    for f in replay:
        print(f.format())
        rc = 1

    # seeded-mutation mode: both historical bugs must be REJECTED
    muts = run_seeded_mutations(report.baseline.trace, seed=args.seed)
    for name, findings in sorted(muts.items()):
        if findings:
            print(
                f"graftsched: mutation {name}: caught "
                f"({findings[0].message})"
            )
        else:
            print(
                f"graftsched: mutation {name}: NOT CAUGHT — the automaton "
                "lost the rule this mutation exercises"
            )
            rc = 1

    # SloPolicy leg: the SLO-aware scheduler (serving/scheduler.py) must
    # emit GC010-clean schedules under mixed-class traffic, and its
    # terminal token streams must match FIFO over the same workload —
    # admission order moves *when* a request runs, never what it
    # generates (per-lane attention + the per-request sampling install)
    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        _run_schedule,
    )
    from neuronx_distributed_llama3_2_tpu.serving.scheduler import (
        SloPolicy,
    )

    mixed = make_engine_factory(mixed=True)
    base = _run_schedule(mixed, None, "fifo-mixed", 200)
    slo = _run_schedule(mixed, SloPolicy(), "slo-mixed", 200)
    for rep in (base, slo):
        for f in rep.findings:
            print(f.format())
            rc = 1
    if slo.streams != base.streams:
        diff = sorted(
            rid for rid in set(base.streams) | set(slo.streams)
            if base.streams.get(rid) != slo.streams.get(rid)
        )
        print(
            "graftsched: STREAM MISMATCH: slo-mixed diverges from "
            f"fifo-mixed on rid(s) {diff}"
        )
        rc = 1
    else:
        print(
            f"graftsched: slo leg: {slo.steps} step(s), "
            f"{slo.actions} action(s), streams identical to fifo"
        )

    if rc == 0:
        print(
            "graftsched: clean "
            f"({1 + len(report.explored)} schedule(s) stream-identical, "
            f"{len(muts)} mutation(s) caught)"
        )
    return rc


if __name__ == "__main__":
    _configure_jax()
    sys.exit(main())
