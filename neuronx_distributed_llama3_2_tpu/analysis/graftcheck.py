"""graftcheck: rule-based analyzer over closed jaxprs and compiled programs.

shardlint (:mod:`.shardlint`) sees source ASTs; this module sees what the
tracer and the compiler actually produced. The serving engine's
hardest-won properties are *program* properties — no materialized
gathered-KV copy (PR 3/6), donation that actually aliases in the
compiled executable (PR 4), steady-state traces with zero host transfers
(PR 4), collective-free paged-decode shard_map regions (PR 6), fp32
widening around the quantized pool (PR 7), program-registry purity on a
fault-free engine (PR 8) — and until now they were enforced by
copy-pasted jaxpr walkers in three test files plus runtime counters.
graftcheck turns each invariant into a named rule over a traced program,
with the same Finding/baseline/suppression model shardlint uses, so the
gate (scripts/graftcheck_gate.py) and suite teardowns
(:func:`audit_programs`) can enforce them everywhere at once.

Rules (see docs/static_analysis.md for the motivating bug behind each):

GC001  a kernel-path decode/verify program materializes the gathered
       ``(b, kv_limit, NKV, D)`` K/V copy the Pallas kernel exists to
       avoid (shape predicate over every sub-jaxpr).
GC002  declared donation dropped at lowering: a ``donate_argnums`` entry
       produced no input-output alias in the lowered program — today
       this only surfaces as a silent perf cliff (double-buffered HBM).
GC003  host-transfer census: a steady-state program traces
       ``device_put``/callback equations (the static twin of the
       ``h2d_uploads`` runtime counter).
GC004  collective audit: no collective primitive inside a
       collective-free ``shard_map`` region (the paged-decode region
       relies on the row-parallel o-projection for its tp reduce), and
       collectives anywhere only on declared mesh axis names.
GC005  quantized-pool arithmetic: values leaving an int8/fp8 array must
       widen to fp32 (converts target f32, dots carry an fp32
       accumulator) — never bf16/f16 arithmetic on low-bit payloads.
       Knob-aware: with ``config.quant_mxu`` on, int8 dots may
       accumulate in int32 (the MXU-native path — scales are applied
       to the fp32 score matrix afterwards); with the knob off that
       same dot is still a finding.
GC006  program-registry purity: a fault-free engine compiles no
       ``checked`` program variants and an undegraded engine no
       gather-fallback variants.
GC007  closed catalog: every ``engine._programs`` key must be derivable
       from the declared :class:`..serving.catalog.CatalogManifest` —
       an out-of-ladder compile is a finding naming the offending key
       and the nearest legal bucket.
GC008  steady-state compile freeze: after prewarm/first traffic marks
       the registry steady (``engine._frozen_keys``), growing the key
       set or re-lowering an existing key at different avals is flagged
       (the static twin of a recompile stall). Ladder-driven gather
       twins on a degraded engine are exempt.
GC009  cost-accounting completeness: a metered engine may not hold a
       program key without a usable device-cost profile
       (serving/accounting.py; checked by :func:`audit_programs`).
GC010  schedule legality: an engine's recorded step-action trace must
       be accepted by the legality automaton in
       :mod:`.graftsched` (verify only after the lookahead drains,
       full-lane syncs and block release only at pipeline-drained
       boundaries, readback lag <= 1, no dispatch into a freed lane).
       The replay entry point is ``graftsched.check_action_trace``;
       it lives in the GC catalogue because it audits *recorded
       engine behavior* at teardown, exactly like audit_programs.
GC011  policy-table freshness: a graftplan policy table may only load
       with its explorer certificate present and GC010-clean, its
       automaton and catalog-ladder fingerprints matching the live
       engine, and every prefill chunk budget on the prefill ladder.
       The check entry point is ``graftplan.check_policy_table`` (the
       loaders raise ``PolicyTableError`` on any finding); it lives in
       the GC catalogue because it gates *loading* a static artifact,
       the mirror image of GC010 auditing a recorded trace.

Suppression: jaxprs have no source lines to annotate, so suppression is
per (program, rule) — pass ``suppress={"GC003", ...}`` to the check
entry points (the gate catalog carries it per entry). Accepted findings
ship in the gate's baseline file (scripts/graftcheck_baseline.txt) with
the same fingerprint-keyed format as shardlint's.

Unlike shardlint this module imports jax (it must trace and lower), but
it never *executes* a program: rules read jaxprs and lowered text only,
so the whole analyzer runs on the CPU tier.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import (
    Any,
    Dict,
    FrozenSet,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Set,
    Tuple,
)

__all__ = [
    "GC_RULES",
    "Finding",
    "all_shapes",
    "audit_programs",
    "check_collectives",
    "check_donation",
    "check_fp32_widening",
    "check_host_transfers",
    "check_no_gather",
    "filter_baseline",
    "read_baseline",
    "walk_eqns",
    "write_baseline",
]

# rule id -> one-line summary (the catalogue the gate prints with --rules)
GC_RULES: Dict[str, str] = {
    "GC001": "kernel-path program materializes a gathered KV copy",
    "GC002": "declared donation dropped at lowering (no input-output alias)",
    "GC003": "host transfer (device_put/callback) in a steady-state program",
    "GC004": "collective in a collective-free region or on an undeclared axis",
    "GC005": (
        "low-bit (quantized-pool) value used without fp32 widening "
        "(int8->int32 dots permitted iff config.quant_mxu)"
    ),
    "GC006": "fault-free engine compiled a checked/gather program variant",
    "GC007": "program key not derivable from the declared catalog manifest",
    "GC008": "registry grew or a key re-lowered after the steady-state freeze",
    "GC009": "cost-accounting engine holds a key without a usable CostProfile",
    "GC010": (
        "recorded step-action trace rejected by the schedule legality "
        "automaton (analysis/graftsched.py)"
    ),
    "GC011": (
        "policy table loaded without a fresh explorer certificate "
        "(missing/unclean certificate, stale automaton or ladder "
        "fingerprint, off-ladder budget; analysis/graftplan.py)"
    ),
}

#: default axis universe for GC004 — kept in sync with parallel/state.py
#: MESH_AXES (shardlint's load_axis_env reads the same source of truth).
DEFAULT_MESH_AXES: FrozenSet[str] = frozenset({"pp", "dp", "cp", "ep", "tp"})

# collective primitives across the jax generations this repo spans
# (0.4.x spells psum "psum2"); axis_index is included — inside a
# collective-free manual region it is as much a cross-rank dependence as
# a psum is.
_COLLECTIVE_PRIMS: FrozenSet[str] = frozenset(
    {
        "psum", "psum2", "pmax", "pmin", "pmean", "ppermute", "pshuffle",
        "pbroadcast", "all_gather", "all_to_all", "reduce_scatter",
        "psum_scatter", "axis_index", "pgather",
    }
)

# host-transfer primitives (GC003): device_put is an explicit host->device
# move smuggled into a trace; the callback family round-trips through the
# host every step. jax.debug.print is its own primitive (debug_print) on
# jax 0.9, no longer a debug_callback; the rest of the family kept its
# names (probed: debug.callback/breakpoint -> debug_callback,
# pure_callback, io_callback, device_put).
_HOST_TRANSFER_PRIMS: FrozenSet[str] = frozenset(
    {
        "device_put", "copy_to_host_async", "callback", "pure_callback",
        "io_callback", "debug_callback", "debug_print",
    }
)

# low-bit storage dtypes of the quantized KV pool (GC005) — kept in sync
# with quantization/kv_cache.py KV_CACHE_DTYPES.
_LOW_BIT_DTYPES: FrozenSet[str] = frozenset(
    {"int8", "float8_e4m3fn", "float8_e5m2"}
)

# primitives that merely MOVE low-bit payloads (no arithmetic): allowed to
# consume int8/fp8 operands without widening. Everything arithmetic must
# go through convert_element_type-to-f32 or an fp32-accumulating dot.
_STRUCTURAL_PRIMS: FrozenSet[str] = frozenset(
    {
        "reshape", "transpose", "broadcast_in_dim", "slice", "dynamic_slice",
        "dynamic_update_slice", "gather", "scatter", "concatenate", "squeeze",
        "rev", "pad", "copy", "select_n", "stop_gradient", "split",
        # pallas ref plumbing (the kernel jaxpr moves int8 tiles through
        # VMEM refs before its in-kernel f32 widen)
        "get", "swap", "masked_load", "masked_swap", "load", "store",
    }
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation on one traced program. ``detail`` is a stable
    locator (primitive name, offending shape, axis …) rather than a line
    number, so the fingerprint survives retraces that reorder equations."""

    rule: str
    program: str  # catalog/registry label, e.g. "pdecode[kv_limit=32]"
    message: str
    hint: str
    detail: str = ""

    @property
    def fingerprint(self) -> str:
        digest = hashlib.sha1(
            f"{self.rule}|{self.program}|{self.detail}".encode()
        ).hexdigest()
        return digest[:12]

    def format(self) -> str:
        return (
            f"{self.program}: {self.rule} {self.message}\n"
            f"    hint: {self.hint}"
        )


# ---------------------------------------------------------------------------
# The recursive jaxpr walker (the one shared implementation of the three
# copy-pasted test walkers)
# ---------------------------------------------------------------------------


def _as_jaxpr(jaxpr_or_closed: Any) -> Any:
    """Accept a ClosedJaxpr, a raw Jaxpr, or anything with a ``.jaxpr``."""
    inner = getattr(jaxpr_or_closed, "jaxpr", None)
    return inner if inner is not None else jaxpr_or_closed


def _sub_jaxprs(eqn: Any) -> Iterator[Any]:
    """Raw sub-jaxprs referenced by an equation's params — covers
    scan/jit/pjit/shard_map/cond (``branches``)/while/custom_vjp/
    pallas_call and anything else that stores a (Closed)Jaxpr, a list of
    them, or a tuple of them."""
    for p in eqn.params.values():
        for x in (p if isinstance(p, (list, tuple)) else [p]):
            if hasattr(x, "jaxpr"):       # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):      # raw Jaxpr
                yield x


def walk_eqns(
    jaxpr_or_closed: Any, path: Tuple[str, ...] = ()
) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield ``(eqn, path)`` for every equation, recursively descending
    into every sub-jaxpr; ``path`` is the tuple of enclosing primitive
    names (so ``"shard_map" in path`` identifies manual regions)."""
    jaxpr = _as_jaxpr(jaxpr_or_closed)
    for eqn in jaxpr.eqns:
        yield eqn, path
        inner_path = path + (eqn.primitive.name,)
        for inner in _sub_jaxprs(eqn):
            yield from walk_eqns(inner, inner_path)


def all_shapes(jaxpr_or_closed: Any) -> Set[Tuple[int, ...]]:
    """Every aval shape appearing on any equation in the program,
    sub-jaxprs included — the shape census the no-gather assertions are
    written against."""
    acc: Set[Tuple[int, ...]] = set()
    for eqn, _path in walk_eqns(jaxpr_or_closed):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
    return acc


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


def check_no_gather(
    jaxpr_or_closed: Any,
    forbidden: Iterable[Tuple[int, ...]],
    program: str = "<program>",
    suppress: Iterable[str] = (),
) -> List[Finding]:
    """GC001: none of the ``forbidden`` aval shapes (the materialized
    gathered-KV copies — full NKV and any per-rank NKV/tp slice) may
    appear anywhere in the program."""
    if "GC001" in suppress:
        return []
    shapes = all_shapes(jaxpr_or_closed)
    out: List[Finding] = []
    for shape in sorted(set(map(tuple, forbidden)) & shapes):
        out.append(
            Finding(
                rule="GC001",
                program=program,
                message=(
                    f"materialized gathered-KV aval {shape} — the paged "
                    "read is not gather-free"
                ),
                hint=(
                    "the Pallas kernel dereferences the block table inside "
                    "its BlockSpec index maps; check _paged_kernel_eligible "
                    "routing and that the trace took the kernel path"
                ),
                detail=str(shape),
            )
        )
    return out


def check_donation(
    lowered: Any,
    donated_leaves: int,
    program: str = "<program>",
    suppress: Iterable[str] = (),
) -> List[Finding]:
    """GC002: every donated array leaf must show up as an input-output
    alias in the lowered program — a ``tf.aliasing_output`` argument
    attribute, or ``jax.buffer_donor`` for sharded arguments (mesh
    lowering can't prove a fixed output pairing up front, so it marks the
    buffer reusable instead; either spelling means the donation held).
    jax silently drops donation when no output matches the donated
    buffer's shape/dtype — the bug only ever surfaces as a perf cliff
    (double-buffered pool HBM), which is exactly why it needs a static
    gate."""
    if "GC002" in suppress or donated_leaves == 0:
        return []
    text = lowered.as_text()
    aliased = text.count("tf.aliasing_output") + text.count("jax.buffer_donor")
    if aliased >= donated_leaves:
        return []
    return [
        Finding(
            rule="GC002",
            program=program,
            message=(
                f"donation dropped: {donated_leaves} donated array leaf(s) "
                f"but only {aliased} input-output alias(es) in the lowered "
                "program"
            ),
            hint=(
                "a donated input aliases only when some output matches its "
                "shape+dtype; a post-donate read, a dtype cast or a dropped "
                "output silently un-donates the buffer (jax warns once, "
                "then double-buffers every step)"
            ),
            detail=f"aliased={aliased}<{donated_leaves}",
        )
    ]


def check_host_transfers(
    jaxpr_or_closed: Any,
    program: str = "<program>",
    suppress: Iterable[str] = (),
) -> List[Finding]:
    """GC003: a steady-state program must trace zero host-transfer
    equations — the static twin of the engine's ``h2d_uploads`` runtime
    counter (a device_put or callback inside the trace is a per-step
    host round trip the zero-upload loop exists to avoid)."""
    if "GC003" in suppress:
        return []
    out: List[Finding] = []
    seen: Set[str] = set()
    for eqn, path in walk_eqns(jaxpr_or_closed):
        name = eqn.primitive.name
        if name not in _HOST_TRANSFER_PRIMS:
            continue
        where = "/".join(path + (name,))
        if where in seen:
            continue
        seen.add(where)
        out.append(
            Finding(
                rule="GC003",
                program=program,
                message=f"host-transfer equation {name!r} in the trace"
                + (f" (inside {'/'.join(path)})" if path else ""),
                hint=(
                    "steady-state decode/verify must dispatch from "
                    "device-resident state only; route host values through "
                    "the engine's _upload funnel at scheduler events, not "
                    "inside the program"
                ),
                detail=where,
            )
        )
    return out


def _eqn_axis_names(eqn: Any) -> Tuple[str, ...]:
    """Axis names a collective equation operates over (string axes only —
    positional/vmap integer axes are not mesh axes)."""
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", None)
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if isinstance(a, str))
    return (axes,) if isinstance(axes, str) else ()


def check_collectives(
    jaxpr_or_closed: Any,
    program: str = "<program>",
    allowed_axes: Optional[FrozenSet[str]] = None,
    collective_free_regions: bool = True,
    suppress: Iterable[str] = (),
) -> List[Finding]:
    """GC004: with ``collective_free_regions`` (the paged-decode
    contract) no collective primitive may appear inside any ``shard_map``
    region of the program — the in-region reduce belongs to the
    row-parallel o-projection *outside* it. Everywhere, collective axis
    names must be members of the declared mesh axis universe."""
    if "GC004" in suppress:
        return []
    allowed = allowed_axes if allowed_axes is not None else DEFAULT_MESH_AXES
    out: List[Finding] = []
    for eqn, path in walk_eqns(jaxpr_or_closed):
        name = eqn.primitive.name
        if name not in _COLLECTIVE_PRIMS:
            continue
        axes = _eqn_axis_names(eqn)
        if collective_free_regions and "shard_map" in path:
            out.append(
                Finding(
                    rule="GC004",
                    program=program,
                    message=(
                        f"collective {name!r} over {list(axes)} inside a "
                        "shard_map region declared collective-free"
                    ),
                    hint=(
                        "the paged-decode manual region must stay "
                        "collective-free — its tp reduce is owned by the "
                        "row-parallel o-projection after attention; move "
                        "the collective outside the region"
                    ),
                    detail=f"region:{name}:{','.join(axes)}",
                )
            )
            continue
        undeclared = [a for a in axes if a not in allowed]
        if undeclared:
            out.append(
                Finding(
                    rule="GC004",
                    program=program,
                    message=(
                        f"collective {name!r} over undeclared mesh "
                        f"axis(es) {undeclared}"
                    ),
                    hint=(
                        "collectives may only name declared mesh axes "
                        "(parallel/state.py MESH_AXES); an unknown axis "
                        "fails only when the trace meets a mesh without it"
                    ),
                    detail=f"axes:{name}:{','.join(undeclared)}",
                )
            )
    return out


def _dtype_name(v: Any) -> str:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return getattr(dt, "name", "")


def check_fp32_widening(
    jaxpr_or_closed: Any,
    program: str = "<program>",
    suppress: Iterable[str] = (),
    quant_mxu: bool = False,
) -> List[Finding]:
    """GC005: every equation consuming an int8/fp8 (quantized-pool)
    operand must either be structural (move the payload), convert it to
    float32, or be a dot with an fp32 accumulator. Arithmetic directly on
    low-bit payloads — or a widen that targets bf16/f16 — silently
    changes serving numerics vs the token-identical contract.

    ``quant_mxu`` makes the rule knob-aware: when the engine's model
    config declares the MXU-native dot (``config.quant_mxu``), an int8
    dot accumulating in int32 is the INTENDED lowering (the k-scale
    column and the requantized q row scale are applied to the fp32
    score matrix after the dot), so that one shape is permitted. With
    the knob off the same dot is still a finding — fp32 widening is
    required exactly iff quant_mxu is off."""
    if "GC005" in suppress:
        return []
    out: List[Finding] = []
    seen: Set[str] = set()
    for eqn, path in walk_eqns(jaxpr_or_closed):
        low = sorted(
            {
                _dtype_name(v)
                for v in eqn.invars
                if _dtype_name(v) in _LOW_BIT_DTYPES
            }
        )
        if not low:
            continue
        name = eqn.primitive.name
        if name in _STRUCTURAL_PRIMS:
            continue
        if any(True for _ in _sub_jaxprs(eqn)):
            continue  # container (scan/pjit/pallas_call/...): judged inside
        bad: Optional[str] = None
        if name == "convert_element_type":
            target = _dtype_name(eqn.outvars[0])
            if target != "float32" and target not in _LOW_BIT_DTYPES:
                bad = f"convert {low[0]} -> {target} (must widen to float32)"
        elif name == "dot_general":
            acc = _dtype_name(eqn.outvars[0])
            if quant_mxu and low == ["int8"] and acc == "int32":
                continue  # MXU-native int8 dot: scales applied post-dot
            if acc != "float32":
                bad = (
                    f"dot_general on {'/'.join(low)} accumulates in "
                    f"{acc or '<unknown>'} (needs "
                    "preferred_element_type=float32, or int32 under "
                    "config.quant_mxu)"
                )
        else:
            bad = f"{name} consumes {'/'.join(low)} without fp32 widening"
        if bad is None:
            continue
        detail = f"{name}:{','.join(low)}"
        if detail in seen:
            continue
        seen.add(detail)
        out.append(
            Finding(
                rule="GC005",
                program=program,
                message=bad,
                hint=(
                    "quantized-pool payloads widen through "
                    "kv_dequantize's astype(float32) * scale formula (the "
                    "kernel fuses the same widen after its block DMA); "
                    "low-bit dots need preferred_element_type=jnp.float32"
                ),
                detail=detail,
            )
        )
    return out


# ---------------------------------------------------------------------------
# Baseline (shardlint-gate file format: <RULE> <program> <fingerprint>)
# ---------------------------------------------------------------------------


def read_baseline(path: str) -> Dict[str, str]:
    """fingerprint -> raw line (comments/blank lines skipped)."""
    import os

    out: Dict[str, str] = {}
    if not os.path.exists(path):
        return out
    with open(path, "r") as fh:
        for raw in fh:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            if len(parts) >= 3:
                out[parts[2]] = line
    return out


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    with open(path, "w") as fh:
        fh.write(
            "# graftcheck baseline: grandfathered findings (fingerprint-"
            "keyed, retrace-proof).\n# Regenerate with: python "
            "scripts/graftcheck_gate.py --write-baseline\n"
            "# Every entry needs a rationale; prefer fixing over "
            "baselining.\n# Format: <RULE> <program> <fingerprint>"
            "  # rationale\n"
        )
        for f in findings:
            fh.write(f"{f.rule} {f.program} {f.fingerprint}\n")


def filter_baseline(
    findings: Sequence[Finding], baseline: Dict[str, str]
) -> List[Finding]:
    """Findings not grandfathered by the baseline."""
    return [f for f in findings if f.fingerprint not in baseline]


# ---------------------------------------------------------------------------
# Engine audit: rules over the serving engine's program registry
# ---------------------------------------------------------------------------


def _registry_label(rec: Any) -> str:
    meta = getattr(rec, "meta", None) or {}
    bits = [f"{k}={meta[k]}" for k in sorted(meta)]
    if getattr(rec, "gather", False):
        bits.append("gather")
    if getattr(rec, "checked", False):
        bits.append("checked")
    return rec.kind + (f"[{','.join(bits)}]" if bits else "")


def _donated_leaf_count(rec: Any) -> int:
    import jax

    total = 0
    for i in rec.donate_argnums:
        if i >= len(rec.example_args):
            continue
        total += sum(
            1
            for leaf in jax.tree.leaves(rec.example_args[i])
            if hasattr(leaf, "shape")
        )
    return total


def _trace_cache_size(rec: Any) -> Optional[int]:
    """Distinct traces held by the record's jit wrapper, read through the
    private-but-stable ``_cache_size`` probe. None when the jax build has
    no probe — GC008's re-lower arm then degrades to registry-growth
    detection only."""
    try:
        return int(rec.jitted._cache_size())
    except Exception:
        return None


def _check_freeze(
    key: Tuple, rec: Any, frozen: FrozenSet, never_degraded: bool
) -> List[Finding]:
    """GC008 body: a key outside the freeze set means the registry grew
    mid-traffic; a frozen key whose trace cache holds more than one entry
    was re-lowered at different avals. Both are the static shadow of a
    production recompile stall. Gather twins on a degraded engine are the
    one legitimate post-freeze compile (the ladder's kernel-shed rung)."""
    from neuronx_distributed_llama3_2_tpu.serving.catalog import format_key

    label = _registry_label(rec)
    if key not in frozen:
        if not never_degraded and rec.gather:
            return []  # ladder shed past the freeze: sanctioned twin
        return [
            Finding(
                rule="GC008",
                program=label,
                message=(
                    f"program key {format_key(key)} compiled after the "
                    "steady-state freeze (registry grew mid-traffic)"
                ),
                hint=(
                    "prewarm should cover every reachable key before "
                    "traffic; extend the ladder or PagedConfig buckets so "
                    "this shape is pre-lowered, or re-run mark_steady() "
                    "after intentional catalog growth"
                ),
                detail="new:" + format_key(key),
            )
        ]
    n = _trace_cache_size(rec)
    if n is not None and n > 1:
        return [
            Finding(
                rule="GC008",
                program=label,
                message=(
                    f"frozen program key {format_key(key)} re-lowered "
                    f"after the freeze ({n} traces in the jit cache — "
                    "dispatch avals drifted)"
                ),
                hint=(
                    "a second trace means some dispatch passed different "
                    "shapes/dtypes than prewarm did; align the dispatch "
                    "args (aval twins) or widen the bucket it pads into"
                ),
                detail=f"relower:{n}",
            )
        ]
    return []


def audit_programs(
    engine: Any, suppress: Iterable[str] = ()
) -> List[Finding]:
    """Run every applicable rule over a :class:`PagedServingEngine`'s
    compiled-program registry — the suite-teardown companion to
    ``BlockAllocator.leak_check`` and ``invariants.audit_engine``.

    Per registry record (``engine.program_registry()``):

    - GC006 on the *key population*: a fault-free engine (no injector, no
      ``detect_nonfinite``) must hold no ``checked`` variants; an engine
      that never climbed the degradation ladder no ``gather`` variants.
    - GC007 on every key: it must be a member of the engine's declared
      catalog manifest expansion (``engine.catalog.keys()``); the finding
      names the nearest legal bucket.
    - GC008 after the steady-state freeze (``engine.mark_steady()`` /
      prewarm): keys compiled after the freeze, or frozen keys whose jit
      trace cache grew past one entry (a re-lower at different avals),
      are findings. Gather twins on a degraded engine are exempt — the
      ladder is allowed to shed to gather mid-traffic.
    - For records that actually dispatched (example avals recorded):
      GC002 on the lowered program's donation aliasing; GC003/GC004 on
      the retraced jaxpr; GC001 on decode/verify programs whose trace
      should have taken the kernel path; GC005 when the pool is
      quantized.

    Returns the (possibly empty) finding list so teardowns can
    ``assert audit_programs(engine) == []``.
    """
    import jax

    suppress = frozenset(suppress)
    findings: List[Finding] = []
    fault_free = engine.injector is None and not engine.paged.detect_nonfinite
    never_degraded = engine.metrics.degradations == 0
    # catalog contract inputs: the manifest is engine-construction state,
    # the freeze set is None until mark_steady()/prewarm() runs. getattr
    # keeps the auditor usable on pre-catalog engine doubles in tests.
    manifest = getattr(engine, "catalog", None)
    legal = manifest.keys() if manifest is not None else None
    frozen = getattr(engine, "_frozen_keys", None)

    for key, rec in engine.program_registry().items():
        label = _registry_label(rec)
        if legal is not None and "GC007" not in suppress and key not in legal:
            from neuronx_distributed_llama3_2_tpu.serving.catalog import (
                format_key,
                nearest_key,
            )

            near = nearest_key(key, legal)
            findings.append(
                Finding(
                    rule="GC007",
                    program=label,
                    message=(
                        f"program key {format_key(key)} is not derivable "
                        "from the declared catalog manifest"
                        + (f" (nearest legal bucket: {near})" if near else "")
                    ),
                    hint=(
                        "widen PagedConfig.kv_buckets/prefill_buckets (or "
                        "the sampling/verify variants) so the ladder covers "
                        "this shape, then refresh the golden with "
                        "graftcheck_gate.py --write-catalog"
                    ),
                    detail=format_key(key),
                )
            )
        if frozen is not None and "GC008" not in suppress:
            findings.extend(_check_freeze(key, rec, frozen, never_degraded))
        if "GC006" not in suppress:
            if fault_free and rec.checked:
                findings.append(
                    Finding(
                        rule="GC006",
                        program=label,
                        message=(
                            "checked program variant compiled on a "
                            "fault-free engine (no injector, "
                            "detect_nonfinite off)"
                        ),
                        hint=(
                            "checked traces add the poison-mask input and "
                            "finite output; a fault-free engine paying "
                            "that cost means _check_logits leaked"
                        ),
                        detail="checked",
                    )
                )
            if never_degraded and rec.gather:
                findings.append(
                    Finding(
                        rule="GC006",
                        program=label,
                        message=(
                            "gather-fallback program variant compiled on "
                            "an engine that never climbed the degradation "
                            "ladder"
                        ),
                        hint=(
                            "the kernel-shed rung (_gather_shed) is the "
                            "only legitimate source of gather-variant "
                            "keys; check _step_model routing"
                        ),
                        detail="gather",
                    )
                )
        if rec.example_args is None:
            continue  # registered but never dispatched: nothing traced
        findings.extend(
            check_donation(
                rec.lower(), _donated_leaf_count(rec), label,
                suppress=suppress,
            )
        )
        closed = jax.make_jaxpr(rec.fn)(*rec.example_args)
        findings.extend(check_host_transfers(closed, label, suppress=suppress))
        findings.extend(
            check_collectives(
                closed, label, collective_free_regions=True, suppress=suppress
            )
        )
        if getattr(engine, "_kv_quantized", False):
            findings.extend(
                check_fp32_widening(
                    closed, label, suppress=suppress,
                    quant_mxu=getattr(
                        engine.model.config, "quant_mxu", False
                    ),
                )
            )
        if rec.kind in ("pdecode", "pverify", "pmixed") and not rec.gather:
            if rec.kind == "pmixed":
                t = int(rec.meta.get("t", 1))
            else:
                t = 1 + int(rec.meta.get("k", 0))
            if engine.model._paged_kernel_eligible(t, None):
                forbidden = engine.model.forbidden_gather_shapes(
                    engine.engine.max_batch, int(rec.meta["kv_limit"])
                )
                findings.extend(
                    check_no_gather(closed, forbidden, label, suppress=suppress)
                )
    if "GC009" not in suppress:
        findings.extend(_check_cost_profiles(engine, frozen))
    return findings


def _check_cost_profiles(engine: Any, frozen) -> List[Finding]:
    """GC009 — cost-profile completeness (graftmeter, serving/accounting):
    once a frozen engine has harvested (``cost_profiles`` is not None),
    every registry key must carry a :class:`CostProfile` with positive
    FLOPs (compute kinds report model FLOPs; move kinds elements moved)
    and positive argument bytes. A missing or degenerate profile means
    the MFU/roofline figures downstream silently undercount."""
    profiles = getattr(engine, "cost_profiles", None)
    if profiles is None or frozen is None:
        return []
    findings: List[Finding] = []
    for key, rec in engine.program_registry().items():
        label = _registry_label(rec)
        prof = profiles.get(key)
        if prof is None:
            findings.append(
                Finding(
                    rule="GC009",
                    program=label,
                    message=(
                        "no CostProfile for a registered program on a "
                        "cost-accounting engine"
                    ),
                    hint=(
                        "ensure_cost_profiles() runs at the end of "
                        "prewarm(); a key compiled after harvest needs a "
                        "re-harvest (or is itself a GC008 finding)"
                    ),
                    detail="missing",
                )
            )
            continue
        bad = []
        if not prof.flops > 0:
            bad.append(f"flops={prof.flops}")
        if not prof.argument_bytes > 0:
            bad.append(f"argument_bytes={prof.argument_bytes}")
        if bad:
            findings.append(
                Finding(
                    rule="GC009",
                    program=label,
                    message=(
                        "degenerate CostProfile ("
                        + ", ".join(bad)
                        + ") — MFU/roofline accounting would undercount"
                    ),
                    hint=(
                        "check serving/accounting.py analytic_cost for "
                        "this program kind and the harvested lowering's "
                        "cost_analysis()"
                    ),
                    detail=prof.flops_source,
                )
            )
    return findings
