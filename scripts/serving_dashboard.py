#!/usr/bin/env python
"""Terminal dashboard over ServingMetrics snapshots (graftscope scrape
surface, docs/serving.md "Observability").

Renders the latest snapshot record as a compact terminal view: request
counters, pool gauges, degradation-ladder state, and the latency
histograms (TTFT / TPOT / step) as p50/p90/p99 rows. Input is jsonl of
``ServingMetrics.snapshot()`` dicts — what ``metrics_log_every`` logs,
what chaos_soak/paged_decode_bench records embed, or what any engine
loop writes with ``json.dumps(m.snapshot(...))``.

Usage:
  python scripts/serving_dashboard.py --file metrics.jsonl        # latest
  python scripts/serving_dashboard.py --file metrics.jsonl --follow
  python scripts/serving_dashboard.py --prom metrics.prom         # exposition
  python scripts/serving_dashboard.py --prom http://host:port/metrics
  python scripts/serving_dashboard.py --demo   # tiny CPU engine, live

``--follow`` tails the input and redraws on every new record; ``--demo``
builds the tiny-model paged engine (CPU), drives a small workload, and
renders as it goes — the zero-hardware smoke of the whole scrape path.
``--prom`` accepts a prometheus text exposition instead of snapshot
jsonl — a file, or an ``http(s)://`` URL scraped from a live
:class:`~serving.server.GraftServer` ``/metrics`` endpoint — and
reconstructs the snapshot shape (flat keys, per-class families,
histogram percentiles re-interpolated from the cumulative buckets)
before rendering the same panels.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

_BAR_WIDTH = 24


def _bar(frac: float, width: int = _BAR_WIDTH) -> str:
    frac = min(max(frac, 0.0), 1.0)
    n = int(round(frac * width))
    return "#" * n + "." * (width - n)


def _hist_row(label: str, h: dict) -> str:
    if not h or not h.get("count"):
        return f"  {label:<10} (no samples)"
    return (
        f"  {label:<10} p50 {h['p50']:>9.3f}  p90 {h['p90']:>9.3f}  "
        f"p99 {h['p99']:>9.3f}  max {h['max']:>9.3f}  (n={h['count']})"
    )


def render_snapshot(snap: dict) -> str:
    """Pure snapshot-dict -> text renderer (unit-tested; the CLI below is
    just a loop around it)."""
    g = snap.get
    util = float(g("block_utilization", 0.0) or 0.0)
    lines = [
        "== serving dashboard ==",
        (
            f"requests   submitted {g('submitted', 0)}  "
            f"finished {g('finished', 0)}  failed {g('failed_requests', 0)}  "
            f"preempted {g('preemptions', 0)}  truncated {g('truncated', 0)}"
        ),
        (
            f"front door queued {g('queued_requests', 0)}  "
            f"streams {g('active_streams', 0)}  "
            f"cancelled {g('cancelled_requests', 0)}"
        ),
        (
            f"decode     steps {g('decode_steps', 0)} "
            f"(async {g('decode_steps_async', 0)}, "
            f"verify {g('verify_steps', 0)})  "
            f"accept_rate {g('accept_rate', 0.0)}  "
            f"prefix_skip {g('prefix_skip_fraction', 0.0)}"
        ),
        (
            f"pool       util {util:.2f} [{_bar(util)}]  "
            f"free {g('free_blocks', '?')}  evictions {g('evictions', 0)}  "
            f"h2d_uploads {g('h2d_uploads', 0)}"
        ),
        (
            f"timing     host {g('host_schedule_ms_per_step', 0.0)} ms/step  "
            f"device_wait {g('device_wait_ms_per_step', 0.0)} ms/step"
        ),
        "latency (ms)",
        _hist_row("ttft", g("ttft_ms", {})),
        _hist_row("tpot", g("tpot_ms", {})),
        _hist_row("step", g("step_latency_ms", {})),
        _hist_row("queue", g("queue_depth", {})),
        (
            f"ladder     level {g('degradation_level', 0)}  "
            f"climbs {g('degradations', 0)}  "
            f"faults {g('faults_injected', 0)}  "
            f"violations {g('audit_violations', 0)}"
        ),
    ]
    accept = g("accept_len")
    if accept and accept.get("count"):
        lines.insert(lines.index(_hist_row("queue", g("queue_depth", {}))),
                     _hist_row("accept", accept))
    # speculation panel (docs/serving.md "Tree speculation"): the
    # packed-tree verify counters plus the per-shape accept-depth mix;
    # only rendered when tree verifies ran, so spec-off and linear-spec
    # snapshots draw unchanged
    tas = g("tree_accept_by_shape") or {}
    if g("tree_verify_steps") or tas:
        anchor = lines.index("latency (ms)")
        lines.insert(anchor, (
            f"tree spec  verifies {g('tree_verify_steps', 0)}  "
            f"nodes {g('tree_draft_tokens', 0)}"
        ))
        for shape in sorted(tas):
            v = tas[shape]
            anchor += 1
            lanes = int(v.get("lanes", 0) or 0)
            mean = (v.get("accepted", 0) / lanes) if lanes else 0.0
            mix = "  ".join(
                f"{d}:{c}" for d, c in sorted(
                    (v.get("by_len") or {}).items(),
                    key=lambda kv: int(kv[0]),
                )
            )
            lines.insert(anchor, (
                f"  {shape:<9} lanes {lanes}  "
                f"mean_accept {mean:.2f}  depth {mix}"
            ))
    # fused mixed-mode step panel (docs/serving.md "Fused mixed-mode
    # step"): dispatches per engine step — the figure fused_step exists
    # to drive toward 1.0 — plus how many dispatches were pmixed. Only
    # rendered for snapshots that carry the counters (newer records).
    if "dispatches_per_step" in snap:
        lines.insert(
            lines.index("latency (ms)"),
            (
                f"dispatch   {g('dispatches_per_step', 0.0)}/step "
                f"(compute {g('compute_dispatches', 0)} over "
                f"{g('engine_steps', 0)} steps, "
                f"mixed {g('mixed_dispatches', 0)})"
            ),
        )
    # graftmeter panels (docs/serving.md "Cost accounting & SLOs"): only
    # rendered when the snapshot carries the cost-accounting keys, so the
    # dashboard still draws pre-graftmeter records
    if g("cost_profiled_programs"):
        budget = float(g("hbm_budget_bytes", 0) or 0)
        foot = float(g("hbm_footprint_bytes", 0) or 0)
        used = foot / budget if budget else 0.0
        gib = 2**30
        lines.append(
            f"capacity   hbm {foot / gib:.2f}/{budget / gib:.2f} GiB "
            f"[{_bar(used)}]  headroom "
            f"{float(g('hbm_headroom_bytes', 0) or 0) / gib:.2f} GiB  "
            f"profiles {g('cost_profiled_programs', 0)}"
        )
    if "mfu_est" in snap:
        lines.append(
            f"mfu        est {g('mfu_est', 0.0)} "
            f"[{_bar(float(g('mfu_est', 0.0) or 0.0))}]  "
            f"achieved {float(g('achieved_flops_per_s', 0.0) or 0.0):.3g} "
            f"FLOP/s  bw_util {g('bandwidth_util_est', 0.0)}  "
            f"pad_waste {g('pad_waste_frac', 0.0)}"
        )
        for key, tag in (("decode_pad_by_rung", "decode"),
                         ("prefill_pad_by_rung", "prefill")):
            rungs = g(key) or {}
            if rungs:
                row = "  ".join(
                    f"{r}:{v['pad_frac']:.2f}"
                    for r, v in sorted(
                        rungs.items(), key=lambda kv: int(kv[0])
                    )
                )
                lines.append(f"  pad/rung {tag:<8} {row}")
    # tiered-KV host-tier panel (docs/serving.md "Tiered KV storage"):
    # only rendered for spill-enabled engines (nonzero budget), so
    # pre-spill records and spill-off engines draw unchanged
    if g("host_tier_budget_bytes"):
        mib = 2**20
        budget = float(g("host_tier_budget_bytes", 0) or 0)
        resident = float(g("host_tier_bytes", 0) or 0)
        hit = float(g("restore_hit_rate", 0.0) or 0.0)
        lines.append(
            f"host tier  {resident / mib:.1f}/{budget / mib:.0f} MiB "
            f"[{_bar(resident / budget if budget else 0.0)}]  "
            f"entries {g('host_tier_entries', 0)}  "
            f"tier_evictions {g('host_tier_evictions', 0)}  "
            f"spilled_nodes {g('spilled_nodes', 0)}"
        )
        lines.append(
            f"  spill    out {g('blocks_spilled', 0)} blocks "
            f"({float(g('spill_bytes', 0) or 0) / mib:.1f} MiB)  "
            f"back {g('blocks_restored', 0)} "
            f"({float(g('restore_bytes', 0) or 0) / mib:.1f} MiB)  "
            f"hit_rate {hit:.2f} [{_bar(hit)}]  "
            f"fallbacks {g('restore_fallbacks', 0)}  "
            f"declined {g('restore_declined', 0)}"
        )
    if "slo_alerts" in snap and (
        g("slo_burn_ttft") or g("slo_burn_tpot") or g("slo_alerts")
    ):
        lines.append(
            f"slo        burn ttft {g('slo_burn_ttft', 0.0)}  "
            f"tpot {g('slo_burn_tpot', 0.0)}  alerts {g('slo_alerts', 0)}"
        )
    # graftserve per-class panels (docs/serving.md "Front door &
    # scheduling"): lifecycle counters and SLO burn per service class;
    # the burn bar saturates at burn 1.0 — exactly consuming the budget
    rbc = g("requests_by_class") or {}
    if rbc:
        row = "  ".join(
            f"{cls}: sub {v.get('submitted', 0)} "
            f"fin {v.get('finished', 0)} fail {v.get('failed', 0)}"
            for cls, v in sorted(rbc.items())
        )
        lines.append(f"classes    {row}")
    sbc = g("slo_burn_by_class") or {}
    for cls in sorted(sbc):
        burns = sbc[cls]
        t = float(burns.get("ttft", 0.0) or 0.0)
        p = float(burns.get("tpot", 0.0) or 0.0)
        lines.append(
            f"  burn/{cls:<9} ttft {t:>7.3f} [{_bar(t)}]  "
            f"tpot {p:>7.3f} [{_bar(p)}]"
        )
    # graftplan policy panel (docs/static_analysis.md "graftplan"): the
    # loaded certified table's id, simulated (from the artifact) vs
    # observed (live SLO monitor) burn per class, and a warning when the
    # table was force-loaded past stale GC011 findings
    if g("policy_table_id"):
        lines.append(f"policy     table {g('policy_table_id')}")
        psb = g("policy_simulated_burn") or {}
        for cls in sorted(psb):
            sim = psb[cls]
            obs = sbc.get(cls) or {}
            lines.append(
                f"  plan/{cls:<9} ttft "
                f"sim {float(sim.get('ttft', 0.0) or 0.0):>7.3f} "
                f"obs {float(obs.get('ttft', 0.0) or 0.0):>7.3f}  tpot "
                f"sim {float(sim.get('tpot', 0.0) or 0.0):>7.3f} "
                f"obs {float(obs.get('tpot', 0.0) or 0.0):>7.3f}"
            )
        if g("policy_table_stale"):
            lines.append(
                "  WARNING: stale certificate (GC011) — re-synthesize "
                "via scripts/graftplan_gate.py --write-table"
            )
    return "\n".join(lines)


def parse_prometheus(text: str) -> dict:
    """Reconstruct a snapshot-shaped dict from a ``ServingMetrics``
    prometheus exposition (the inverse of ``metrics.prometheus()``, to
    rendering fidelity): flat ``serving_<key>`` samples become snapshot
    keys, the per-class labelled families fold back into
    ``requests_by_class`` / ``slo_burn_by_class``, the per-rung pad
    families into ``*_pad_by_rung``, and each histogram's cumulative
    buckets are re-interpolated into the p50/p90/p99 summary rows the
    dashboard draws (the ``max`` of an exposition is unknowable — the
    highest nonzero bucket edge stands in)."""
    import re

    flat: dict = {}
    hists: dict = {}
    labelled = re.compile(r'^(\w+)\{(.*)\} (\S+)$')

    def _num(s: str):
        v = float(s)
        return int(v) if v.is_integer() else v

    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = labelled.match(line)
        if m:
            name, labels_s, val = m.groups()
            labels = dict(re.findall(r'(\w+)="([^"]*)"', labels_s))
            if name == "serving_requests_class":
                flat.setdefault("requests_by_class", {}) \
                    .setdefault(labels["class"], {})[labels["event"]] = \
                    _num(val)
            elif name == "serving_slo_burn_class":
                flat.setdefault("slo_burn_by_class", {}) \
                    .setdefault(labels["class"], {})[labels["objective"]] = \
                    float(val)
            elif name.endswith("_pad_frac_rung"):
                kind = "decode" if name.startswith("serving_decode") else "prefill"
                flat.setdefault(f"{kind}_pad_by_rung", {}) \
                    .setdefault(int(labels["rung"]), {})["pad_frac"] = \
                    float(val)
            elif name == "serving_policy_table_info":
                flat["policy_table_id"] = labels.get("table_id", "")
            elif name == "serving_policy_simulated_burn_class":
                flat.setdefault("policy_simulated_burn", {}) \
                    .setdefault(labels["class"], {})[labels["objective"]] = \
                    float(val)
            elif name == "serving_tree_accept_lanes_shape":
                d = flat.setdefault("tree_accept_by_shape", {}) \
                    .setdefault(labels["shape"],
                                {"lanes": 0, "accepted": 0, "by_len": {}})
                d["by_len"][int(labels["len"])] = _num(val)
                d["lanes"] = sum(d["by_len"].values())
            elif name == "serving_tree_accept_tokens_shape":
                d = flat.setdefault("tree_accept_by_shape", {}) \
                    .setdefault(labels["shape"],
                                {"lanes": 0, "accepted": 0, "by_len": {}})
                d["accepted"] = _num(val)
            elif name == "serving_roofline_mfu_rung":
                flat.setdefault("mfu_by_rung", {}) \
                    .setdefault(int(labels["rung"]), {})["roofline_mfu"] = \
                    float(val)
            elif name.endswith("_bucket") and "le" in labels:
                base = name[: -len("_bucket")]
                if labels["le"] != "+Inf":
                    hists.setdefault(base, {"buckets": []})["buckets"] \
                        .append((float(labels["le"]), float(val)))
            continue
        parts = line.split()
        if len(parts) != 2:
            continue
        name, val = parts
        if name.endswith("_sum") or name.endswith("_count"):
            base, _, kind = name.rpartition("_")
            if base.removeprefix("serving_") in (
                "ttft_ms", "tpot_ms", "step_latency_ms", "accept_len",
                "queue_depth",
            ):
                hists.setdefault(base, {"buckets": []})[kind] = float(val)
                continue
        if name.startswith("serving_"):
            try:
                flat[name[len("serving_"):]] = _num(val)
            except ValueError:
                pass

    def _pct(buckets, count: float, q: float) -> float:
        target = q * count
        prev_edge, cum = 0.0, 0.0
        for edge, cumulative in buckets:
            n = cumulative - cum
            if n > 0 and cumulative >= target:
                frac = (target - cum) / n
                return round(prev_edge + (edge - prev_edge) * frac, 4)
            cum = cumulative
            prev_edge = edge
        return round(prev_edge, 4)

    for base, h in hists.items():
        key = base[len("serving_"):] if base.startswith("serving_") else base
        count = h.get("count", 0.0)
        buckets = sorted(h["buckets"])
        flat[key] = {
            "count": int(count),
            "mean": round(h.get("sum", 0.0) / count, 4) if count else 0.0,
            "max": buckets[-1][0] if buckets else 0.0,
            "p50": _pct(buckets, count, 0.50) if count else 0.0,
            "p90": _pct(buckets, count, 0.90) if count else 0.0,
            "p99": _pct(buckets, count, 0.99) if count else 0.0,
        }
    return flat


def _last_record(path: str) -> dict:
    last = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                last = json.loads(line)
            except json.JSONDecodeError:
                continue
    if last is None:
        raise SystemExit(f"no snapshot records in {path}")
    return last


def _demo() -> int:
    # the tiny-model CPU engine: exercises the full snapshot -> render
    # path (and leaves a trace artifact) without hardware
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

    cfg = LLAMA_CONFIGS["tiny"]
    params = LlamaForCausalLM(cfg).init(jax.random.key(0))
    eng = InferenceEngine(
        cfg, params, max_batch=4, max_seq_len=64, buckets=[8, 16, 32]
    )
    paged = PagedServingEngine(
        eng, GenerationConfig(max_new_tokens=16),
        PagedConfig(
            block_size=8, num_blocks=32, trace_enabled=True,
            # fused mixed-mode demo coverage: the dispatch panel row
            # shows a nonzero pmixed count
            fused_step=True, prefill_chunk_tokens=4,
            # tree-speculation demo coverage: packed-tree drafts on the
            # repetitive prompts below light up the speculation panel
            spec_draft_tokens=3, spec_tree=True,
            # tiered-KV demo coverage: the host-tier panel renders (the
            # small demo workload never evicts, so the gauges stay 0)
            spill_enabled=True, host_tier_bytes=64 << 20,
            # graftplan demo coverage: a TablePolicy engine so the
            # policy panel renders (the demo table loads below)
            step_policy="table",
            # graftmeter demo coverage: SLO burn gauges render on the
            # dashboard (loose targets, so the demo stays alert-free)
            slo_ttft_p99_ms=60_000.0, slo_tpot_p99_ms=60_000.0,
            slo_eval_steps=4,
        ),
    )
    # the demo engine warms lazily (no prewarm), so harvest explicitly to
    # light up the capacity/MFU panels
    paged.ensure_cost_profiles()
    # graftplan policy panel demo: an uncertified hand-built table on the
    # demo engine's own ladders, force-loaded past GC011 — the panel
    # renders with simulated-vs-observed burn AND the stale-certificate
    # warning line (the honest rendering of a table nothing certified)
    from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
        _stamp,
        automaton_fingerprint,
        ladder_fingerprint,
    )

    demo_table = _stamp({
        "version": 1,
        "generator": "serving_dashboard --demo",
        "ladder": {
            "prefill": list(paged._prefill_buckets),
            "kv": list(paged._kv_buckets),
        },
        "fingerprints": {
            "automaton": automaton_fingerprint(),
            "ladder": ladder_fingerprint(
                paged._prefill_buckets, paged._kv_buckets
            ),
            "trace": "0" * 40,
        },
        "vector": {"class_weight": {"interactive": 0.0, "batch": 1.0}},
        "objective": {"simulated_burn_by_class": {
            "batch": {"ttft": 0.0, "tpot": 0.0},
            "interactive": {"ttft": 0.02, "tpot": 0.0},
        }},
    })
    paged.load_policy_table(demo_table, strict=False)
    rng = __import__("numpy").random.default_rng(0)
    for i, n in enumerate((5, 11, 7, 19)):
        # alternate repetitive prompts (the prompt-lookup drafter
        # proposes, so the speculation panel renders) with random ones
        if i % 2:
            pat = rng.integers(1, 9, size=3).tolist()
            prompt = (pat * (n // 3 + 1))[:n]
        else:
            prompt = rng.integers(1, cfg.vocab_size, size=n).tolist()
        paged.submit(
            prompt,
            # mixed classes/tenants: the per-class panels render in the
            # demo (burns stay 0.0 under the loose targets)
            service_class="interactive" if i % 2 else "batch",
            tenant=("acme", "globex")[i % 2],
        )
    alive, steps = True, 0
    while alive:
        alive = paged.step()
        steps += 1
        if steps % 4 == 0 or not alive:
            print(render_snapshot(
                paged.metrics.snapshot(paged.allocator, paged.index)
            ))
            print()
    trace = paged.export_trace("serving_demo_trace.json")
    print(f"trace written to {trace} (load in https://ui.perfetto.dev)")
    return 0


def _read_prom(src: str) -> str:
    if src.startswith(("http://", "https://")):
        from urllib.request import urlopen

        with urlopen(src, timeout=10) as resp:
            return resp.read().decode()
    with open(src) as f:
        return f.read()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--file", help="jsonl file of snapshot records")
    ap.add_argument(
        "--prom",
        help="prometheus exposition input: a file, or an http(s):// "
        "/metrics endpoint (a live GraftServer scrape)",
    )
    ap.add_argument("--follow", action="store_true",
                    help="tail the input and redraw on new records")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="poll interval for --follow (seconds)")
    ap.add_argument("--demo", action="store_true",
                    help="drive the tiny CPU engine and render live")
    args = ap.parse_args(argv)
    if args.demo:
        return _demo()
    if not args.file and not args.prom:
        ap.error("--file, --prom, or --demo required")
    if args.file and args.prom:
        ap.error("--file and --prom are mutually exclusive")

    def _render_once() -> None:
        if args.prom:
            print(render_snapshot(parse_prometheus(_read_prom(args.prom))))
        else:
            print(render_snapshot(_last_record(args.file)))

    if not args.follow:
        _render_once()
        return 0
    if args.prom:
        while True:
            sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
            _render_once()
            time.sleep(args.interval)
    last_size = -1
    while True:
        try:
            size = os.path.getsize(args.file)
        except OSError:
            size = -1
        if size != last_size and size > 0:
            last_size = size
            sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
            print(render_snapshot(_last_record(args.file)))
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
