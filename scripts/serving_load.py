#!/usr/bin/env python
"""graftserve load harness: simulated clients against the front door.

Three legs, all seeded and CPU-hosted on the tiny model:

1. **Policy comparison** — the same mixed-class/mixed-tenant workload is
   burst- (smoke) or wave- (full) submitted into otherwise identical
   engines, one under ``FifoPolicy`` and one under ``SloPolicy`` (and,
   with ``--policy-table``, a third under a certified graftplan
   ``TablePolicy``), and the run is gated on the graftscope histograms
   the engines observe into:

   - every request finishes (zero failed/stuck), the action trace is
     GC010-clean, ``audit_engine`` and ``leak_check`` are clean;
   - the per-class TTFT histograms saw every request of their class;
   - **interactive-class p99 TTFT improves under SloPolicy** while
     aggregate tokens/step stays within 5% of FIFO — the acceptance bar
     for an SLO scheduler that reorders admission without taxing
     throughput.

2. **Tiered-KV churn** — a multi-tenant workload (many simulated users
   sharing a few long system prompts) over a pool sized to force
   eviction, run through a spill-disabled (recompute) engine and a
   spill-enabled one; gated on byte-identical token streams, restore
   hit rate > 0, strictly fewer prefill dispatches than the recompute
   baseline, tokens/step no worse, and zero h2d uploads outside the
   metered restore path (docs/serving.md "Tiered KV storage").

3. **Async streaming clients** — a :class:`~serving.server.GraftServer`
   drives a third engine while concurrent asyncio clients submit, stream
   tokens, and cancel mid-stream; gated on zero open streams at the end,
   the expected cancel count, and the same invariant/automaton sweep.

Usage:
    python scripts/serving_load.py            # full: 10k+ requests
    python scripts/serving_load.py --smoke    # tier-1: small, seconds
    python scripts/serving_load.py --requests 2000 --seed 3
    python scripts/serving_load.py --policy-table auto   # + table leg

``--smoke`` is what ``tests/test_server.py`` runs in-process; the full
run is one command through the chip tool.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

TENANTS = ("acme", "globex", "initech")


def _configure_jax() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("NXDT_KERNEL_MODE", "reference")
    import jax

    from neuronx_distributed_llama3_2_tpu.utils.runtime import (
        enable_compile_cache,
    )

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


_STATE = None


def make_engine_factory():
    """engine_factory(policy_name) -> fresh tiny engine (shared params).

    The largest prefill bucket (32) equals ``max_batch *
    prefill_chunk_tokens``, so SloPolicy's bucket-quantized prefill
    budget admits the same chunk wave FIFO runs — the throughput
    comparison isolates *admission order*, which is the thing under
    test."""
    global _STATE
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
        _STATE = (cfg, params)
    cfg, params = _STATE

    def factory(policy_name: str, table_path=None,
                policy=None) -> PagedServingEngine:
        return PagedServingEngine(
            InferenceEngine(
                cfg, params, max_batch=4, max_seq_len=64,
                buckets=[16, 32],
            ),
            GenerationConfig(max_new_tokens=6),
            PagedConfig(
                block_size=8, num_blocks=64, prefill_chunk_tokens=8,
                step_policy=policy_name,
                # graftplan: a certified table artifact for the "table"
                # leg, loaded at construction under GC011
                policy_table_path=table_path,
                # tight TTFT objective (burns under the burst, exercising
                # the burn-feedback path) but a loose TPOT one: a burning
                # TPOT clamps SloPolicy's prefill budget, which is decode
                # protection, not what this comparison measures
                slo_ttft_p99_ms=50.0, slo_tpot_p99_ms=10_000.0,
                slo_eval_steps=8,
            ),
            policy=policy,
        )

    return factory


def make_churn_engine(spill: bool):
    """Tiered-KV churn engine: same tiny model as the policy legs but a
    deliberately small pool, so a multi-tenant workload sharing a few
    system prompts keeps evicting the shared prefixes between re-uses.
    ``spill=True`` arms the host tier with ``restore_crossover`` forced
    sky-high — tiny-model prefill FLOPs are nearly free, and the leg
    measures the restore *mechanism* (hit rate, skipped prefill work,
    byte-identity), not the pricing policy."""
    global _STATE
    import jax

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
        cfg = LLAMA_CONFIGS["tiny"]
        params = LlamaForCausalLM(cfg).init(jax.random.key(0))
        _STATE = (cfg, params)
    cfg, params = _STATE
    return PagedServingEngine(
        InferenceEngine(
            cfg, params, max_batch=4, max_seq_len=64, buckets=[16, 32],
        ),
        GenerationConfig(max_new_tokens=6),
        PagedConfig(
            block_size=8, num_blocks=28, prefill_chunk_tokens=8,
            spill_enabled=spill,
            host_tier_bytes=(1 << 30) if spill else 0,
            restore_crossover=1e9 if spill else 1.0,
        ),
    )


def make_churn_workload(seed: int, n_requests: int, n_system: int = 8):
    """Multi-tenant churn: ``n_requests`` simulated users sharing
    ``n_system`` long system prompts (3 blocks each — together larger
    than the churn engine's cached headroom, so every prefix keeps
    getting evicted between re-uses), round-robin across tenants.
    Every request is the system prompt plus a short per-user tail."""
    import numpy as np

    rng = np.random.default_rng(seed)
    vocab = 128
    system = [
        rng.integers(0, vocab, size=(24,)).tolist() for _ in range(n_system)
    ]
    work = []
    for i in range(n_requests):
        tail = rng.integers(0, vocab, size=(int(rng.integers(4, 9)),))
        work.append((
            system[i % n_system] + tail.tolist(),
            "batch", TENANTS[i % len(TENANTS)],
        ))
    return work


def run_churn_leg(workload, wave: int = 0) -> int:
    """The tiered-KV acceptance leg: the same churn workload through a
    spill-disabled (recompute) engine and a spill-enabled one. Gates:

    - both runs finish everything, audits/automaton/leaks clean;
    - token streams **byte-identical** — restore-over-recompute is an
      optimization, never a numerics change;
    - the spill run restores (restore hit rate > 0) and dispatches
      **strictly fewer** prefill programs than the recompute baseline
      (restored prefixes skip re-prefill);
    - tokens/step no worse than the recompute baseline (5% floor, same
      tolerance as the policy legs);
    - zero steady-state uploads outside the metered restore path: every
      h2d upload past the baseline's count is accounted in
      ``restore_uploads``.
    """
    rc = 0
    runs = {}
    for spill in (False, True):
        eng = make_churn_engine(spill)
        todo = list(workload)
        if not wave:
            for prompt, sc, tenant in todo:
                eng.submit(prompt, service_class=sc, tenant=tenant)
            todo = []
        alive = True
        while alive or todo:
            for prompt, sc, tenant in todo[:wave]:
                eng.submit(prompt, service_class=sc, tenant=tenant)
            todo = todo[wave:] if wave else []
            alive = eng.step()
        label = "churn-spill" if spill else "churn-base"
        rc |= _audit_clean(eng, label)
        m = eng.metrics
        if m.failed_requests or m.finished != len(workload):
            print(
                f"serving_load: GATE: {label} finished={m.finished} "
                f"failed={m.failed_requests} of {len(workload)}"
            )
            rc = 1
        steps = eng._step_index
        runs[spill] = {
            "outs": {r: tuple(req.out) for r, req in eng._finished.items()},
            "tokens_per_step": (
                sum(len(r.out) for r in eng._finished.values()) / steps
                if steps else 0.0
            ),
            "prefill_chunks": m.prefill_chunks,
            "h2d_uploads": m.h2d_uploads,
            "restore_uploads": m.restore_uploads,
            "restore_hits": m.restore_hits,
            "blocks_spilled": m.blocks_spilled,
            "blocks_restored": m.blocks_restored,
            "restore_hit_rate": m.snapshot()["restore_hit_rate"],
        }
    base, spl = runs[False], runs[True]
    if base["outs"] != spl["outs"]:
        bad = [
            r for r in base["outs"]
            if base["outs"][r] != spl["outs"].get(r)
        ]
        print(
            f"serving_load: GATE: churn token streams diverge under spill "
            f"(rids {bad[:8]}{'...' if len(bad) > 8 else ''})"
        )
        rc = 1
    if not spl["restore_hits"] > 0:
        print(
            "serving_load: GATE: churn spill leg never restored "
            f"(spilled={spl['blocks_spilled']})"
        )
        rc = 1
    if not spl["prefill_chunks"] < base["prefill_chunks"]:
        print(
            "serving_load: GATE: restored prefixes did not skip prefill "
            f"dispatches: spill {spl['prefill_chunks']} vs "
            f"baseline {base['prefill_chunks']}"
        )
        rc = 1
    if base["tokens_per_step"] and (
        spl["tokens_per_step"] < 0.95 * base["tokens_per_step"]
    ):
        print(
            "serving_load: GATE: churn tokens/step regressed >5% under "
            f"spill: {spl['tokens_per_step']:.3f} vs "
            f"{base['tokens_per_step']:.3f}"
        )
        rc = 1
    extra = spl["h2d_uploads"] - base["h2d_uploads"]
    if extra > spl["restore_uploads"]:
        print(
            "serving_load: GATE: spill leg made h2d uploads outside the "
            f"metered restore path: +{extra} vs restore_uploads="
            f"{spl['restore_uploads']}"
        )
        rc = 1
    print(
        f"serving_load: churn leg: {len(workload)} requests, "
        f"{spl['blocks_spilled']} spilled / {spl['blocks_restored']} "
        f"restored (hit rate {spl['restore_hit_rate']}); prefill "
        f"dispatches {base['prefill_chunks']} -> {spl['prefill_chunks']}; "
        f"tokens/step {base['tokens_per_step']:.3f} -> "
        f"{spl['tokens_per_step']:.3f}"
    )
    return rc


def make_workload(seed: int, n_interactive: int, n_batch: int):
    """Seeded mixed workload: (prompt, service_class, tenant) triples.
    Batch requests lead and interactive trail — the FIFO worst case an
    admission reorderer exists to fix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    vocab = 128
    work = []
    for i in range(n_batch):
        n = int(rng.integers(20, 29))
        work.append((
            rng.integers(0, vocab, size=(n,)).tolist(),
            "batch", TENANTS[i % len(TENANTS)],
        ))
    for i in range(n_interactive):
        n = int(rng.integers(4, 9))
        work.append((
            rng.integers(0, vocab, size=(n,)).tolist(),
            "interactive", TENANTS[i % len(TENANTS)],
        ))
    return work


def _audit_clean(eng, label: str) -> int:
    """Invariant sweep at teardown: auditor + leak_check + automaton."""
    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        check_action_trace,
    )
    from neuronx_distributed_llama3_2_tpu.serving import audit_engine

    rc = 0
    for v in audit_engine(eng):
        print(f"serving_load: {label}: AUDIT: {v}")
        rc = 1
    for bid in eng.allocator.leak_check():
        print(f"serving_load: {label}: LEAK: block {bid}")
        rc = 1
    for f in check_action_trace(eng):
        print(f"serving_load: {label}: {f.format()}")
        rc = 1
    return rc


def run_policy_leg(factory, policy_name: str, workload, wave: int = 0,
                   table_path=None):
    """Run one engine under ``policy_name`` over the workload. ``wave``
    > 0 paces submissions (that many per step — open-loop arrivals, so
    the queue stays bounded on 10k-request runs); 0 bursts everything
    up front (smoke: maximal head-of-line pressure)."""
    eng = factory(policy_name, table_path)
    todo = list(workload)
    if not wave:
        for prompt, sc, tenant in todo:
            eng.submit(prompt, service_class=sc, tenant=tenant)
        todo = []
    t0 = time.perf_counter()
    alive = True
    while alive or todo:
        for prompt, sc, tenant in todo[:wave]:
            eng.submit(prompt, service_class=sc, tenant=tenant)
        todo = todo[wave:] if wave else []
        alive = eng.step()
    wall = time.perf_counter() - t0
    m = eng.metrics
    steps = eng._step_index
    gen_tokens = sum(len(r.out) for r in eng._finished.values())
    stats = {
        "finished": m.finished,
        "failed": m.failed_requests,
        "steps": steps,
        "wall_s": round(wall, 3),
        "tokens_per_step": (gen_tokens / steps) if steps else 0.0,
        "ttft_by_class": {
            cls: h.snapshot() for cls, h in sorted(m.hist_ttft_by_class.items())
        },
        "tpot_by_class": {
            cls: h.snapshot() for cls, h in sorted(m.hist_tpot_by_class.items())
        },
        "slo_burn_by_class": dict(m.slo_burn_by_class),
    }
    rc = _audit_clean(eng, policy_name)
    return eng, stats, rc


def check_comparison(workload, fifo_stats, cand_stats,
                     label: str = "slo") -> int:
    """The fifo-vs-candidate acceptance gates (see module docstring):
    the same bar for SloPolicy and for a graftplan TablePolicy leg."""
    rc = 0
    n_int = sum(1 for _, sc, _ in workload if sc == "interactive")
    n_bat = len(workload) - n_int
    for name, stats in (("fifo", fifo_stats), (label, cand_stats)):
        if stats["failed"] or stats["finished"] != len(workload):
            print(
                f"serving_load: GATE: {name} finished={stats['finished']} "
                f"failed={stats['failed']} of {len(workload)}"
            )
            rc = 1
        got_int = stats["ttft_by_class"].get("interactive", {}).get("count", 0)
        got_bat = stats["ttft_by_class"].get("batch", {}).get("count", 0)
        if (got_int, got_bat) != (n_int, n_bat):
            print(
                f"serving_load: GATE: {name} ttft histogram counts "
                f"({got_int} interactive, {got_bat} batch) != submitted "
                f"({n_int}, {n_bat})"
            )
            rc = 1
    fifo_p99 = fifo_stats["ttft_by_class"]["interactive"]["p99"]
    cand_p99 = cand_stats["ttft_by_class"]["interactive"]["p99"]
    if not cand_p99 < fifo_p99:
        print(
            f"serving_load: GATE: interactive p99 TTFT did not improve: "
            f"{label} {cand_p99}ms vs fifo {fifo_p99}ms"
        )
        rc = 1
    tps_f, tps_c = fifo_stats["tokens_per_step"], cand_stats["tokens_per_step"]
    if tps_f and tps_c < 0.95 * tps_f:
        print(
            f"serving_load: GATE: tokens/step regressed >5%: "
            f"{label} {tps_c:.3f} vs fifo {tps_f:.3f}"
        )
        rc = 1
    print(
        f"serving_load: interactive p99 TTFT {fifo_p99:.1f}ms (fifo) -> "
        f"{cand_p99:.1f}ms ({label}); tokens/step {tps_f:.3f} -> {tps_c:.3f}"
    )
    return rc


def synthesize_policy_table(fifo_eng, factory, workload, out_path,
                            seed: int = 0) -> str:
    """``--policy-table auto``: the full offline graftplan workflow on
    THIS harness's engine geometry — record (the drained FIFO leg),
    synthesize over a bounded window of the recorded spans, certify
    live on a small replay engine, write the artifact. A table
    synthesized elsewhere (e.g. the gate's golden, built on a different
    bucket ladder) would be rejected under GC011 at load, so the staged
    10k-request leg must carry its own certified table."""
    import json

    from neuronx_distributed_llama3_2_tpu.analysis import graftplan

    rec = fifo_eng.export_workload()
    # the search cost is per-simulated-request; a 256-span window keeps
    # synthesis seconds even on the 10k run while preserving class mix
    rec.requests = rec.requests[:256]
    rec.trace = {
        k: rec.trace[k] for k in ("steps", "actions") if k in rec.trace
    }
    synth = graftplan.synthesize(rec, seed=seed)
    table = graftplan.build_table(rec, synth)

    cert_requests = list(workload)[:12]

    def cert_factory(policy):
        eng = factory("fifo", None, policy)
        for prompt, sc, tenant in cert_requests:
            eng.submit(prompt, service_class=sc, tenant=tenant)
        return eng

    table = graftplan.certify_table(table, cert_factory, max_steps=400)
    with open(out_path, "w") as fh:
        json.dump(table, fh, indent=2, sort_keys=True)
        fh.write("\n")
    cert = table["certificate"]
    print(
        f"serving_load: policy table {table['table_id'][:12]} "
        f"({100 * synth.improvement:+.2f}% simulated, gc010_clean="
        f"{cert['gc010_clean']}) -> {out_path}"
    )
    return out_path


async def run_async_leg(factory, n_clients: int, seed: int) -> int:
    """Concurrent asyncio clients against a GraftServer: submit, stream,
    and cancel every 5th request after two tokens."""
    import numpy as np

    from neuronx_distributed_llama3_2_tpu.serving import GraftServer

    rng = np.random.default_rng(seed)
    eng = factory("slo")
    rc = 0
    cancelled = []

    async def client(srv: GraftServer, i: int, prompt) -> None:
        sc = "interactive" if i % 3 == 0 else "batch"
        rid = srv.submit(
            prompt, service_class=sc, tenant=TENANTS[i % len(TENANTS)]
        )
        cancel_at = 2 if i % 5 == 4 else None
        got = 0
        async for _tok in srv.stream(rid):
            got += 1
            if cancel_at is not None and got >= cancel_at:
                srv.cancel(rid)
                cancelled.append(rid)
        resp = srv.response(rid)
        if cancel_at is not None:
            assert resp["error"] is not None, resp
            assert resp["error"]["type"] == "cancelled", resp
        else:
            assert resp["status"] == "finished", resp

    async with GraftServer(eng, idle_poll_s=0.002) as srv:
        prompts = [
            rng.integers(0, 128, size=(int(rng.integers(4, 24)),)).tolist()
            for _ in range(n_clients)
        ]
        await asyncio.gather(*(
            client(srv, i, p) for i, p in enumerate(prompts)
        ))
        snap = srv.snapshot()

    n_cancel = sum(1 for i in range(n_clients) if i % 5 == 4)
    if len(cancelled) != n_cancel:
        print(
            f"serving_load: GATE: async leg cancelled {len(cancelled)} "
            f"!= expected {n_cancel}"
        )
        rc = 1
    if snap["active_streams"] != 0:
        print(
            f"serving_load: GATE: async leg left "
            f"{snap['active_streams']} open streams"
        )
        rc = 1
    if snap["cancelled_requests"] != n_cancel:
        print(
            f"serving_load: GATE: cancelled_requests gauge "
            f"{snap['cancelled_requests']} != {n_cancel}"
        )
        rc = 1
    rc |= _audit_clean(eng, "async")
    print(
        f"serving_load: async leg: {n_clients} clients, "
        f"{n_cancel} cancels, {snap['finished']} finished, "
        f"active_streams={snap['active_streams']}"
    )
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke", action="store_true",
        help="tier-1 mode: small burst workload (seconds, in-process)",
    )
    ap.add_argument(
        "--requests", type=int, default=None,
        help="total requests for the comparison leg (default 10000 full, "
        "32 smoke)",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--clients", type=int, default=None,
        help="async streaming clients (default requests//10, min 12)",
    )
    ap.add_argument(
        "--policy-table", default=None, metavar="PATH",
        help="run a third comparison leg under a certified graftplan "
        "policy table (step_policy='table'); 'auto' synthesizes + "
        "certifies one from the FIFO leg's recorded workload first",
    )
    args = ap.parse_args(argv)

    total = args.requests or (32 if args.smoke else 10_000)
    n_interactive = max(total // 4, 1)
    n_batch = total - n_interactive
    wave = 0 if args.smoke else 50
    clients = args.clients or max(12, total // 10 if args.smoke else 500)

    factory = make_engine_factory()
    workload = make_workload(args.seed, n_interactive, n_batch)
    rc = 0
    fifo_eng, fifo_stats, rc_f = run_policy_leg(
        factory, "fifo", workload, wave
    )
    _, slo_stats, rc_s = run_policy_leg(factory, "slo", workload, wave)
    rc |= rc_f | rc_s
    rc |= check_comparison(workload, fifo_stats, slo_stats)
    if args.policy_table:
        if args.policy_table == "auto":
            out_dir = os.environ.get("SERVING_TRACE_DIR")
            if out_dir:
                os.makedirs(out_dir, exist_ok=True)
            else:
                import tempfile

                out_dir = tempfile.mkdtemp(prefix="graftplan_")
            table_path = synthesize_policy_table(
                fifo_eng, factory, workload,
                os.path.join(out_dir, "policy_table.json"), seed=args.seed,
            )
        else:
            table_path = args.policy_table
        _, tab_stats, rc_t = run_policy_leg(
            factory, "table", workload, wave, table_path=table_path
        )
        rc |= rc_t
        rc |= check_comparison(workload, fifo_stats, tab_stats, label="table")
    churn_n = 24 if args.smoke else max(total // 4, 2000)
    rc |= run_churn_leg(
        make_churn_workload(args.seed, churn_n), wave=wave
    )
    rc |= asyncio.run(run_async_leg(factory, clients, args.seed))
    print(f"serving_load: {'FAIL' if rc else 'clean'} "
          f"({total} requests, {clients} async clients)")
    return rc


if __name__ == "__main__":
    _configure_jax()
    sys.exit(main())
