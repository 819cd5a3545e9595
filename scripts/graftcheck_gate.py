#!/usr/bin/env python
"""graftcheck CI gate: trace the serving engine's representative programs
and enforce the GC001-GC010 program-level rules.

Usage:
    python scripts/graftcheck_gate.py                   # run the catalog
    python scripts/graftcheck_gate.py --list            # list catalog entries
    python scripts/graftcheck_gate.py --rules           # print the catalogue
    python scripts/graftcheck_gate.py --list-rules      # alias of --rules
    python scripts/graftcheck_gate.py --write-baseline
    python scripts/graftcheck_gate.py --catalog-diff    # manifest vs registry
    python scripts/graftcheck_gate.py --write-catalog   # refresh the golden
    python scripts/graftcheck_gate.py --costs-diff      # cost table vs golden
    python scripts/graftcheck_gate.py --write-costs     # refresh cost golden

Where shardlint_gate.py lints source ASTs, this gate lints *programs*: it
builds tiny CPU-hosted serving engines, runs a few requests so the real
program registry populates, audits it (``analysis.graftcheck.
audit_programs`` — donation aliasing, host-transfer census, collective
audit, registry purity), and direct-traces the decode/verify/tp=2/int8
variants for the shape- and dtype-level rules. Exit status is nonzero iff
a finding is NOT in the baseline file. Baselining is an explicit,
reviewed act: run with ``--write-baseline`` and commit with a rationale.

The ``catalog-*`` entries enforce the GC007/GC008 bounded-catalog
contract end to end: a prewarmed engine is driven through a deliberately
heterogeneous workload (mixed prompt lengths straddling the chunk size,
spec verify, int8, tp=2) and the resulting program registry must be
*byte-identical* to the declared manifest expansion — which itself must
match the checked-in golden ``scripts/graftcheck_catalog.txt``. Ladder
changes are therefore reviewed diffs: run ``--write-catalog`` and commit
the golden alongside the PagedConfig change.

The ``costs-*`` flags do the same for graftmeter's device-cost ledger
(GC009; serving/accounting.py): the *analytic* CostProfile table over the
catalog's prewarm keys — backend-independent arithmetic, so the golden
``scripts/graftcheck_costs.txt`` is stable across XLA versions — must
match the checked-in golden. A cost drift means the model dimensions,
ladder, or cost formulas changed; refresh with ``--write-costs`` and a
rationale. The prewarmed catalog entries additionally assert (GC009)
that every registered program carries a usable harvested CostProfile.

The tier-1 suite runs this gate as
``tests/test_graftcheck.py::test_self_audit`` — no separate CI plumbing.

Registering a new traced program: add a ``(name, fn)`` entry to
``CATALOG`` below returning a finding list (use the ``check_*`` helpers,
or build an engine and return ``audit_programs(engine)``); per-entry rule
opt-outs go through the helpers' ``suppress=`` argument, accepted
findings through the baseline file.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# CPU-hosted like tests/conftest.py: 8 virtual devices (the tp=2 catalog
# entries slice the first two), set before jax initializes its backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("NXDT_KERNEL_MODE", "reference")
if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
# persistent compile cache so repeat gate runs skip XLA (the engine
# entries are the only ones that compile)
from neuronx_distributed_llama3_2_tpu.utils.runtime import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

from neuronx_distributed_llama3_2_tpu.analysis.graftcheck import (  # noqa: E402
    GC_RULES,
    Finding,
    audit_programs,
    check_collectives,
    check_fp32_widening,
    check_host_transfers,
    check_no_gather,
    filter_baseline,
    read_baseline,
    write_baseline,
)
from neuronx_distributed_llama3_2_tpu.serving.catalog import (  # noqa: E402
    format_key,
    read_catalog_file,
    write_catalog_file,
)

DEFAULT_BASELINE = os.path.join(
    REPO_ROOT, "scripts", "graftcheck_baseline.txt"
)
DEFAULT_CATALOG = os.path.join(
    REPO_ROOT, "scripts", "graftcheck_catalog.txt"
)
DEFAULT_COSTS = os.path.join(
    REPO_ROOT, "scripts", "graftcheck_costs.txt"
)

_TINY = None
_PARAMS = None


def _tiny():
    """(kernel config, params) — shared across catalog entries."""
    global _TINY, _PARAMS
    if _TINY is None:
        import dataclasses

        from neuronx_distributed_llama3_2_tpu.models.llama import (
            LLAMA_CONFIGS,
            LlamaForCausalLM,
        )

        _TINY = dataclasses.replace(
            LLAMA_CONFIGS["tiny"], use_paged_kernel=True
        )
        _PARAMS = LlamaForCausalLM(_TINY).init(jax.random.key(0))
    return _TINY, _PARAMS


def _decode_trace(model, params, b=4, kv_limit=32, nb=16, bs=8, w=8):
    cache = model.init_paged_cache(nb, bs)
    return jax.make_jaxpr(
        lambda p, c, t, ps, tb: model.decode_step(
            p, c, t, ps, tb, kv_limit=kv_limit, pos_cap=63
        )
    )(
        params, cache, jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, w), jnp.int32),
    )


def _verify_trace(model, params, k, b=4, kv_limit=32, nb=16, bs=8, w=8):
    cache = model.init_paged_cache(nb, bs)
    return jax.make_jaxpr(
        lambda p, c, t, ps, tb, dl: model.verify_step(
            p, c, t, ps, tb, dl, kv_limit=kv_limit, pos_cap=63
        )
    )(
        params, cache, jnp.zeros((b, k + 1), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b, w), jnp.int32),
        jnp.zeros((b,), jnp.int32),
    )


def _trace_rules(
    closed, name, model, b=4, kv_limit=32, quantized=False, quant_mxu=False
):
    out = []
    out.extend(
        check_no_gather(
            closed, model.forbidden_gather_shapes(b, kv_limit), name
        )
    )
    out.extend(check_host_transfers(closed, name))
    out.extend(check_collectives(closed, name))
    if quantized:
        out.extend(check_fp32_widening(closed, name, quant_mxu=quant_mxu))
    return out


def _catalog_engine(prewarm=True):
    """The strictest single configuration the registry audit runs under:
    int8 pool + MXU-native int8 dot + fused on-device sampling +
    speculative verify + chunked prefill + async lookahead, prewarmed so
    the full manifest is compiled before first traffic. (quant_mxu makes
    GC005's knob-aware arm load-bearing; on_device_sampling makes the
    cfg=lane program family the audited one.)"""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    cfg, params = _tiny()
    return PagedServingEngine(
        InferenceEngine(
            cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16]
        ),
        GenerationConfig(max_new_tokens=6),
        PagedConfig(
            block_size=8, num_blocks=32, kv_cache_dtype="int8",
            quant_mxu=True, on_device_sampling=True,
            spec_draft_tokens=4, prefill_chunk_tokens=6,
            trace_enabled=True, trace_buffer_steps=64, prewarm=prewarm,
        ),
    )


def _catalog_fused_engine(prewarm=True):
    """``fused_step`` twin of the catalog-int8 engine: same ladder, int8
    pool, spec verify, chunked prefill, async lookahead — but every
    cached>0 admission routes through the one-dispatch ``pmixed`` grid,
    so the psfx suffix-pair family leaves the manifest entirely. The
    entry asserts that shrink (fused manifest strictly smaller than the
    unfused psfx×pdecode expansion) on top of the usual byte-identity
    contract."""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    cfg, params = _tiny()
    return PagedServingEngine(
        InferenceEngine(
            cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16]
        ),
        GenerationConfig(max_new_tokens=6),
        PagedConfig(
            block_size=8, num_blocks=32, kv_cache_dtype="int8",
            quant_mxu=True, on_device_sampling=True,
            spec_draft_tokens=4, prefill_chunk_tokens=6,
            fused_step=True,
            trace_enabled=True, trace_buffer_steps=64, prewarm=prewarm,
        ),
    )


def _catalog_spill_engine(prewarm=True):
    """Tiered-KV twin of the catalog-int8 engine: same strict knob set
    plus ``spill_enabled`` over a deliberately small pool, so the churn
    drive below actually evicts through the D2H spill path and restores
    on the prefix re-hit. ``restore_crossover`` is forced sky-high
    because tiny-model prefill FLOPs are nearly free — the gate is about
    the program/catalog contract (GC007: block_save/block_restore in the
    manifest iff spill), not the pricing policy."""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    cfg, params = _tiny()
    return PagedServingEngine(
        InferenceEngine(
            cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16]
        ),
        GenerationConfig(max_new_tokens=6),
        PagedConfig(
            block_size=8, num_blocks=16, kv_cache_dtype="int8",
            quant_mxu=True, on_device_sampling=True,
            spec_draft_tokens=4, prefill_chunk_tokens=6,
            spill_enabled=True, host_tier_bytes=1 << 30,
            restore_crossover=1e9,
            trace_enabled=True, trace_buffer_steps=64, prewarm=prewarm,
        ),
    )


def _catalog_tree_engine(prewarm=True):
    """``spec_tree`` twin of the catalog-int8 engine: same strict knob
    set, but the verify rungs of the kv × k ladder compile as packed-tree
    ("ptree") programs — the ancestor-masked verify forward with the
    parents/node-length operands — and the linear pverify family leaves
    the manifest entirely (same key count, different program per rung).
    The drive below mixes repetitive prompts (so the branching NGram
    drafter actually proposes trees and the ptree programs dispatch)
    with random ones, and the recorded VERIFY actions carry the
    ``tree``/``nodes`` meta that graftsched's GC010 arm bounds-checks."""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    cfg, params = _tiny()
    return PagedServingEngine(
        InferenceEngine(
            cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16]
        ),
        GenerationConfig(max_new_tokens=6),
        PagedConfig(
            block_size=8, num_blocks=32, kv_cache_dtype="int8",
            quant_mxu=True, on_device_sampling=True,
            spec_draft_tokens=4, spec_tree=True,
            prefill_chunk_tokens=6,
            trace_enabled=True, trace_buffer_steps=64, prewarm=prewarm,
        ),
    )


def _catalog_tp2_engine(prewarm=True):
    """tp=2 catalog twin (caller owns the mesh): bf16 pool, chunked
    prefill, single-bucket ladder — small enough that the 9-key manifest
    compiles in seconds yet still proves the contract holds when the
    programs are shard_mapped."""
    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    cfg, params = _tiny()
    return PagedServingEngine(
        InferenceEngine(
            cfg, params, max_batch=2, max_seq_len=16, buckets=[8]
        ),
        GenerationConfig(max_new_tokens=4),
        PagedConfig(
            block_size=8, num_blocks=16, prefill_chunk_tokens=3,
            prewarm=prewarm,
        ),
    )


def _drive_mixed(engine, lens, seed=0):
    """Deliberately heterogeneous traffic: prompt lengths straddling the
    chunk size (whole-prefill and chunk-walk admissions), multiple
    prefill buckets and kv rungs, spec verify if armed."""
    cfg, _ = _tiny()
    rng = np.random.default_rng(seed)
    for n in lens:
        engine.submit(rng.integers(0, cfg.vocab_size, size=(n,)).tolist())
    engine.run_to_completion()


def _catalog_drift(name, engine, catalog_path=DEFAULT_CATALOG):
    """The GC007/GC008 gate arm: registry must equal the manifest
    expansion exactly (both directions), and the manifest must equal the
    checked-in golden entry. Returns findings in the same
    baseline-filterable shape as the rule checkers."""
    findings = []
    label = f"gate:{name}"
    reg = {format_key(k) for k in engine.program_registry()}
    legal = {format_key(k) for k in engine.catalog.keys()}
    for line in sorted(reg - legal):
        findings.append(Finding(
            rule="GC007", program=label,
            message=f"registry key {line} is outside the manifest expansion",
            hint="an out-of-ladder compile reached _register_program; widen "
                 "the PagedConfig ladder or fix the dispatch padding",
            detail=f"extra:{line}",
        ))
    for line in sorted(legal - reg):
        findings.append(Finding(
            rule="GC007", program=label,
            message=f"manifest key {line} was never compiled "
                    "(prewarm left a hole in the catalog)",
            hint="prewarm() must cover every gather-free manifest key; "
                 "check CatalogManifest.prewarm_keys() against the "
                 "dispatch sites",
            detail=f"missing:{line}",
        ))
    golden = read_catalog_file(catalog_path)
    want = engine.catalog.lines()
    if name not in golden:
        findings.append(Finding(
            rule="GC008", program=label,
            message=f"no golden manifest entry '{name}' in {catalog_path}",
            hint="run scripts/graftcheck_gate.py --write-catalog and commit "
                 "the refreshed golden",
            detail=f"golden-missing:{name}",
        ))
    elif golden[name] != want:
        for line in sorted(set(want) - set(golden[name])):
            findings.append(Finding(
                rule="GC008", program=label,
                message=f"manifest key {line} is not in the golden catalog "
                        "(ladder grew without a reviewed golden refresh)",
                hint="if the ladder change is intentional, run "
                     "--write-catalog and commit the golden with a rationale",
                detail=f"golden-add:{line}",
            ))
        for line in sorted(set(golden[name]) - set(want)):
            findings.append(Finding(
                rule="GC008", program=label,
                message=f"golden catalog key {line} is no longer in the "
                        "manifest (ladder shrank without a golden refresh)",
                hint="if the ladder change is intentional, run "
                     "--write-catalog and commit the golden with a rationale",
                detail=f"golden-drop:{line}",
            ))
    return findings


def _sched_trace_findings(name, engine):
    """The GC010 arm: replay the driven engine's recorded step-action
    trace through graftsched's legality automaton (same teardown shape
    as audit_programs), re-keyed into gate findings."""
    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        check_action_trace,
    )

    return [
        Finding(
            rule=f.rule, program=f"gate:{name}",
            message=f"{f.where}: {f.message}", hint=f.hint, detail=f.detail,
        )
        for f in check_action_trace(engine)
    ]


def _cost_lines(engine):
    """Deterministic analytic cost-table lines for the engine's catalog
    prewarm keys (no compiles, no XLA figures — see --write-costs)."""
    from neuronx_distributed_llama3_2_tpu.serving.accounting import (
        analytic_profiles,
        cost_table_lines,
    )

    return cost_table_lines(analytic_profiles(engine))


def _costs_drift(name, engine, costs_path=DEFAULT_COSTS):
    """The GC009 golden arm: the analytic cost table must match the
    checked-in ``graftcheck_costs.txt`` entry line for line."""
    findings = []
    label = f"gate:{name}"
    golden = read_catalog_file(costs_path)
    want = _cost_lines(engine)
    if name not in golden:
        findings.append(Finding(
            rule="GC009", program=label,
            message=f"no golden cost-table entry '{name}' in {costs_path}",
            hint="run scripts/graftcheck_gate.py --write-costs and commit "
                 "the refreshed golden",
            detail=f"golden-missing:{name}",
        ))
        return findings
    for line in sorted(set(want) - set(golden[name])):
        findings.append(Finding(
            rule="GC009", program=label,
            message=f"cost-table line {line!r} is not in the golden "
                    "(model dims, ladder, or cost formulas drifted)",
            hint="if the change is intentional, run --write-costs and "
                 "commit the golden with a rationale",
            detail=f"costs-add:{line}",
        ))
    for line in sorted(set(golden[name]) - set(want)):
        findings.append(Finding(
            rule="GC009", program=label,
            message=f"golden cost-table line {line!r} is no longer "
                    "produced (model dims, ladder, or formulas drifted)",
            hint="if the change is intentional, run --write-costs and "
                 "commit the golden with a rationale",
            detail=f"costs-drop:{line}",
        ))
    return findings


def entry_catalog():
    """Prewarmed int8+spec+chunked+async engine under heterogeneous
    traffic: full registry audit (GC001-GC009) plus the byte-identity
    checks registry == manifest == golden and analytic cost table ==
    golden. Runs while no mesh is live."""
    engine = _catalog_engine()
    # lengths straddle chunk=6 (whole-prefill and chunk-walk), cross the
    # 8/16 prefill buckets, and push positions across the kv rungs
    _drive_mixed(engine, (3, 5, 7, 13, 20))
    assert engine.metrics.steadystate_compiles == 0, (
        "catalog engine compiled past the freeze: "
        f"{engine.metrics.steadystate_compiles}"
    )
    return (
        audit_programs(engine)
        + _sched_trace_findings("catalog-int8", engine)
        + _catalog_drift("catalog-int8", engine)
        + _costs_drift("catalog-int8", engine)
    )


def entry_catalog_fused():
    """The fused_step twin under the same heterogeneous traffic: GC001-
    GC010 over the pmixed-bearing registry, byte-identity against its own
    golden entry, plus the fused-shrink contract — routing chunked
    prefill through the mixed grid must leave the manifest STRICTLY
    smaller than the unfused psfx×pdecode expansion on the same ladder
    (one mixed_t rung per kv bucket replaces the whole suffix-pair
    product)."""
    import dataclasses

    engine = _catalog_fused_engine()
    fused_keys = set(engine.catalog.keys())
    unfused = dataclasses.replace(engine.catalog, fused_step=False)
    assert not any(k[0] == "psfx" for k in fused_keys), (
        "fused manifest still declares suffix-prefill keys"
    )
    assert any(k[0] == "pmixed" for k in fused_keys), (
        "fused manifest declares no pmixed keys"
    )
    assert len(fused_keys) < len(set(unfused.keys())), (
        f"fused manifest ({len(fused_keys)} keys) is not strictly smaller "
        f"than the unfused expansion ({len(set(unfused.keys()))} keys)"
    )
    _drive_mixed(engine, (3, 5, 7, 13, 20))
    assert engine.metrics.steadystate_compiles == 0, (
        "fused catalog engine compiled past the freeze: "
        f"{engine.metrics.steadystate_compiles}"
    )
    assert engine.metrics.mixed_dispatches > 0, (
        "fused catalog engine never dispatched a pmixed program"
    )
    return (
        audit_programs(engine)
        + _sched_trace_findings("catalog-fused", engine)
        + _catalog_drift("catalog-fused", engine)
        + _costs_drift("catalog-fused", engine)
    )


def entry_catalog_spill():
    """The spill_enabled twin: GC001-GC010 over a registry that carries
    the block_save/block_restore movement programs, byte-identity against
    its own golden entry, and a churn drive that proves the tiered-KV
    path end to end — blocks spill D2H during eviction pressure, a
    prefix re-hit restores H2D instead of re-prefilling, the recorded
    action trace replays RESTORE edges through graftsched's automaton,
    and the D2H drain adds zero steady-state compiles or unmetered
    uploads (every restore upload is accounted in ``restore_uploads``)."""
    engine = _catalog_spill_engine()
    cfg, _ = _tiny()
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, size=(16,)).tolist()
    tail = lambda n: rng.integers(0, cfg.vocab_size, size=(n,)).tolist()
    # seed the shared prefix, churn the pool past eviction, re-hit it
    engine.submit(shared + tail(3))
    engine.run_to_completion()
    for _ in range(6):
        engine.submit(tail(13))
    engine.run_to_completion()
    engine.submit(shared + tail(3))
    engine.run_to_completion()
    m = engine.metrics
    assert m.steadystate_compiles == 0, (
        "spill catalog engine compiled past the freeze: "
        f"{m.steadystate_compiles}"
    )
    assert m.blocks_spilled > 0, (
        "churn drive never spilled a block (pool too large or LRU broken)"
    )
    assert m.restore_hits > 0, (
        "prefix re-hit never restored from the host tier"
    )
    assert m.restore_uploads > 0 and m.h2d_uploads >= m.restore_uploads, (
        "restore uploads not metered through the h2d funnel: "
        f"restore={m.restore_uploads} h2d={m.h2d_uploads}"
    )
    return (
        audit_programs(engine)
        + _sched_trace_findings("catalog-spill", engine)
        + _catalog_drift("catalog-spill", engine)
        + _costs_drift("catalog-spill", engine)
    )


def entry_catalog_tree():
    """The spec_tree twin: GC001-GC010 over the ptree-bearing registry
    (GC010's tree-meta arm bounds every recorded tree VERIFY's node
    count), byte-identity against its own golden entry, and a drive with
    repetitive traffic that proves the packed-tree verify actually
    dispatches — trees proposed, one packed upload per verify, zero
    steady-state compiles, and no linear pverify key anywhere in the
    manifest."""
    engine = _catalog_tree_engine()
    keys = set(engine.catalog.keys())
    assert not any(k[0] == "pverify" for k in keys), (
        "spec_tree manifest still declares linear pverify keys"
    )
    assert any(k[0] == "ptree" for k in keys), (
        "spec_tree manifest declares no ptree keys"
    )
    cfg, _ = _tiny()
    rng = np.random.default_rng(7)
    # period-3 repetition drafts well under prompt lookup (the trie
    # drafter branches at the run tails); random fillers keep the
    # admission mix heterogeneous like the other catalog drives
    motif = rng.integers(0, cfg.vocab_size, size=(3,)).tolist()
    for n in (3, 5, 7, 13, 20):
        engine.submit((motif * 7)[:n] if n % 2 else
                      rng.integers(0, cfg.vocab_size, size=(n,)).tolist())
    engine.run_to_completion()
    m = engine.metrics
    assert m.steadystate_compiles == 0, (
        "tree catalog engine compiled past the freeze: "
        f"{m.steadystate_compiles}"
    )
    assert m.tree_verify_steps > 0, (
        "repetitive drive never dispatched a packed-tree verify"
    )
    assert m.tree_draft_tokens > 0, (
        "tree verifies dispatched but no nodes were ever offered"
    )
    return (
        audit_programs(engine)
        + _sched_trace_findings("catalog-tree", engine)
        + _catalog_drift("catalog-tree", engine)
        + _costs_drift("catalog-tree", engine)
    )


def entry_catalog_tp2():
    """Same contract under a pure-tp=2 mesh: the prewarmed 9-key manifest
    must bound the shard_mapped registry exactly."""
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        destroy_model_parallel,
        initialize_model_parallel,
    )

    initialize_model_parallel(
        tensor_model_parallel_size=2, devices=jax.devices()[:2]
    )
    try:
        engine = _catalog_tp2_engine()
        _drive_mixed(engine, (2, 5, 9))
        assert engine.metrics.steadystate_compiles == 0, (
            "tp2 catalog engine compiled past the freeze: "
            f"{engine.metrics.steadystate_compiles}"
        )
        return (
            audit_programs(engine)
            + _sched_trace_findings("catalog-tp2", engine)
            + _catalog_drift("catalog-tp2", engine)
            + _costs_drift("catalog-tp2", engine)
        )
    finally:
        destroy_model_parallel()


def entry_decode():
    """decode t=1 kernel trace (tp=1)."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    cfg, params = _tiny()
    model = LlamaDecode(cfg)
    return _trace_rules(_decode_trace(model, params), "decode", model)


def entry_decode_int8():
    """decode t=1 trace over the int8 pool: GC005 on the dequant path."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    cfg, params = _tiny()
    model = LlamaDecode(cfg)
    cache = model.init_paged_cache(16, 8, kv_cache_dtype="int8")
    closed = jax.make_jaxpr(
        lambda p, c, t, ps, tb: model.decode_step(
            p, c, t, ps, tb, kv_limit=32, pos_cap=63
        )
    )(
        params, cache, jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4, 8), jnp.int32),
    )
    return _trace_rules(closed, "decode-int8", model, quantized=True)


def entry_decode_int8_mxu():
    """decode t=1 trace, int8 pool + ``config.quant_mxu``: the int8→int32
    MXU dot must pass the knob-aware GC005 — and must FAIL the knob-off
    rule (proving the permitted shape is really in the trace and the
    rule kept its teeth for quant_mxu=False engines)."""
    import dataclasses

    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    cfg, params = _tiny()
    model = LlamaDecode(dataclasses.replace(cfg, quant_mxu=True))
    cache = model.init_paged_cache(16, 8, kv_cache_dtype="int8")
    closed = jax.make_jaxpr(
        lambda p, c, t, ps, tb: model.decode_step(
            p, c, t, ps, tb, kv_limit=32, pos_cap=63
        )
    )(
        params, cache, jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4, 8), jnp.int32),
    )
    out = _trace_rules(
        closed, "decode-int8-mxu", model, quantized=True, quant_mxu=True
    )
    knob_off = check_fp32_widening(closed, "decode-int8-mxu")
    if not any(f.rule == "GC005" for f in knob_off):
        out.append(Finding(
            rule="GC005", program="decode-int8-mxu",
            message="quant_mxu trace shows no int8 dot (knob-off GC005 is "
                    "clean) — the MXU-native path silently fell back to "
                    "the widened dot",
            hint="check paged_flash_decode's quant_mxu plumb-through from "
                 "LlamaConfig.quant_mxu",
            detail="mxu-dot-missing",
        ))
    return out


def entry_verify_t1():
    """verify t=1 (k=1 draft) kernel trace."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    cfg, params = _tiny()
    model = LlamaDecode(cfg)
    return _trace_rules(_verify_trace(model, params, k=1), "verify-t1", model)


def entry_verify_t4():
    """verify t=4 (k=4 draft block) kernel trace."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode

    cfg, params = _tiny()
    model = LlamaDecode(cfg)
    return _trace_rules(_verify_trace(model, params, k=4), "verify-t4", model)


def entry_decode_tp2():
    """decode t=1 trace under a pure-tp=2 mesh: GC001 at full NKV *and*
    the per-rank NKV/2 slice, GC004 over the kernel's shard_map region."""
    from neuronx_distributed_llama3_2_tpu.inference.model import LlamaDecode
    from neuronx_distributed_llama3_2_tpu.parallel.state import (
        destroy_model_parallel,
        initialize_model_parallel,
    )

    cfg, params = _tiny()
    initialize_model_parallel(
        tensor_model_parallel_size=2, devices=jax.devices()[:2]
    )
    try:
        model = LlamaDecode(cfg)
        return _trace_rules(
            _decode_trace(model, params), "decode-tp2", model
        )
    finally:
        destroy_model_parallel()


# the program catalog: (name, thunk) -> findings. The catalog-int8 entry
# runs first (it must run while no mesh is live); the tp entries manage
# their own meshes, with catalog-tp2 last.
CATALOG = (
    ("catalog-int8", entry_catalog),
    ("catalog-fused", entry_catalog_fused),
    ("catalog-spill", entry_catalog_spill),
    ("catalog-tree", entry_catalog_tree),
    ("decode", entry_decode),
    ("decode-int8", entry_decode_int8),
    ("decode-int8-mxu", entry_decode_int8_mxu),
    ("verify-t1", entry_verify_t1),
    ("verify-t4", entry_verify_t4),
    ("decode-tp2", entry_decode_tp2),
    ("catalog-tp2", entry_catalog_tp2),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="rewrite the baseline to accept all current findings",
    )
    ap.add_argument(
        "--rules", "--list-rules", dest="rules", action="store_true",
        help="print the rule catalogue (GC001-GC010)",
    )
    ap.add_argument(
        "--list", action="store_true", help="list program-catalog entries"
    )
    ap.add_argument("--catalog-file", default=DEFAULT_CATALOG)
    ap.add_argument(
        "--write-catalog", action="store_true",
        help="rewrite the golden manifest from the declared ladders "
             "(no compiles — the manifest is construction-time state)",
    )
    ap.add_argument(
        "--catalog-diff", action="store_true",
        help="print manifest-vs-registry-vs-golden drift for the "
             "catalog-* entries and exit nonzero on any mismatch",
    )
    ap.add_argument("--costs-file", default=DEFAULT_COSTS)
    ap.add_argument(
        "--write-costs", action="store_true",
        help="rewrite the golden analytic cost table (no compiles — "
             "analytic profiles are construction-time arithmetic)",
    )
    ap.add_argument(
        "--costs-diff", action="store_true",
        help="print analytic-cost-table-vs-golden drift for the "
             "catalog-* entries and exit nonzero on any mismatch",
    )
    args = ap.parse_args(argv)

    if args.write_catalog:
        # prewarm=False: the manifest is pure construction-time state, so
        # refreshing the golden never waits on XLA
        from neuronx_distributed_llama3_2_tpu.parallel.state import (
            destroy_model_parallel,
            initialize_model_parallel,
        )

        entries = {
            "catalog-int8": _catalog_engine(prewarm=False).catalog,
            "catalog-fused": _catalog_fused_engine(prewarm=False).catalog,
            "catalog-spill": _catalog_spill_engine(prewarm=False).catalog,
            "catalog-tree": _catalog_tree_engine(prewarm=False).catalog,
        }
        initialize_model_parallel(
            tensor_model_parallel_size=2, devices=jax.devices()[:2]
        )
        try:
            entries["catalog-tp2"] = _catalog_tp2_engine(
                prewarm=False
            ).catalog
        finally:
            destroy_model_parallel()
        write_catalog_file(args.catalog_file, entries)
        n = sum(len(m.lines()) for m in entries.values())
        print(f"wrote {n} manifest key(s) to {args.catalog_file}")
        return 0

    if args.write_costs:
        # prewarm=False twins of --write-catalog: the analytic table
        # needs only the manifest keys and the engine dimensions
        from neuronx_distributed_llama3_2_tpu.parallel.state import (
            destroy_model_parallel,
            initialize_model_parallel,
        )

        entries = {
            "catalog-int8": _cost_lines(_catalog_engine(prewarm=False)),
            "catalog-fused": _cost_lines(
                _catalog_fused_engine(prewarm=False)
            ),
            "catalog-spill": _cost_lines(
                _catalog_spill_engine(prewarm=False)
            ),
            "catalog-tree": _cost_lines(
                _catalog_tree_engine(prewarm=False)
            ),
        }
        initialize_model_parallel(
            tensor_model_parallel_size=2, devices=jax.devices()[:2]
        )
        try:
            entries["catalog-tp2"] = _cost_lines(
                _catalog_tp2_engine(prewarm=False)
            )
        finally:
            destroy_model_parallel()
        with open(args.costs_file, "w") as fh:
            fh.write(
                "# graftmeter golden analytic cost table: per-program "
                "FLOPs/bytes the device-cost\n# ledger computes for each "
                "gate entry's catalog (GC009 contract; "
                "serving/accounting.py).\n# Analytic figures only — "
                "backend-independent, so drift means model dims, the\n"
                "# ladder, or the cost formulas changed. Regenerate "
                "with:\n#     python scripts/graftcheck_gate.py "
                "--write-costs\n# Format: <entry> <program key> "
                "flops=.. bytes=.. arg=.. src=..\n"
            )
            for name in sorted(entries):
                for line in entries[name]:
                    fh.write(f"{name} {line}\n")
        n = sum(len(v) for v in entries.values())
        print(f"wrote {n} cost line(s) to {args.costs_file}")
        return 0

    if args.costs_diff:
        rc = 0
        from neuronx_distributed_llama3_2_tpu.parallel.state import (
            destroy_model_parallel,
            initialize_model_parallel,
        )

        drift = _costs_drift(
            "catalog-int8", _catalog_engine(prewarm=False), args.costs_file
        )
        drift += _costs_drift(
            "catalog-fused", _catalog_fused_engine(prewarm=False),
            args.costs_file,
        )
        drift += _costs_drift(
            "catalog-spill", _catalog_spill_engine(prewarm=False),
            args.costs_file,
        )
        drift += _costs_drift(
            "catalog-tree", _catalog_tree_engine(prewarm=False),
            args.costs_file,
        )
        initialize_model_parallel(
            tensor_model_parallel_size=2, devices=jax.devices()[:2]
        )
        try:
            drift += _costs_drift(
                "catalog-tp2", _catalog_tp2_engine(prewarm=False),
                args.costs_file,
            )
        finally:
            destroy_model_parallel()
        if not drift:
            print("costs: analytic table == golden")
            return 0
        for f in drift:
            sign = "-" if f.detail.startswith(
                ("costs-drop:", "golden-missing:")
            ) else "+"
            print(f"{f.program.split(':', 1)[1]}: {sign} "
                  f"{f.detail.split(':', 1)[1]}  [{f.rule}]")
        return 1

    if args.catalog_diff:
        rc = 0
        for name, fn in CATALOG:
            if not name.startswith("catalog-"):
                continue
            got = [f for f in fn() if f.rule in ("GC007", "GC008")]
            if not got:
                print(f"{name}: registry == manifest == golden")
                continue
            rc = 1
            for f in got:
                sign = "-" if f.detail.startswith(
                    ("missing:", "golden-drop:")
                ) else "+"
                print(f"{name}: {sign} {f.detail.split(':', 1)[1]}"
                      f"  [{f.rule}]")
        return rc

    if args.rules:
        for rule, summary in sorted(GC_RULES.items()):
            print(f"{rule}  {summary}")
        return 0
    if args.list:
        for name, fn in CATALOG:
            print(f"{name}  {(fn.__doc__ or '').splitlines()[0]}")
        return 0

    findings = []
    for name, fn in CATALOG:
        got = fn()
        print(f"graftcheck: {name}: {len(got)} finding(s)")
        findings.extend(got)

    if args.write_baseline:
        write_baseline(args.baseline, findings)
        print(f"wrote {len(findings)} finding(s) to {args.baseline}")
        return 0

    baseline = read_baseline(args.baseline)
    new = filter_baseline(findings, baseline)
    old = len(findings) - len(new)

    for f in new:
        print(f.format())
    if old:
        print(f"{old} baselined finding(s) suppressed ({args.baseline})")
    if new:
        print(
            f"graftcheck: {len(new)} new finding(s). Fix them, suppress the "
            "rule for that program in the catalog entry, or baseline with "
            "--write-baseline and a commit rationale."
        )
        return 1
    print(f"graftcheck: clean ({len(findings)} total, {old} baselined)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
