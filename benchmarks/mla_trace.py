"""What the sarvam cell's own per-layer metrics read beside
``program_trace.py`` and ``moe_trace.py``: device seconds under a scope path
inside given programs, the dispatch records' ``rows`` and ``kv_bucket`` (which
the program writes since PR 33), the routing tap's ``routed_local``, and the
detail scopes' seconds added to the traced line's breakdown.

A program without these (no ``latent_up`` scope, no ``rows`` in a dispatch
record, no ``routed_local``) makes every function here return ``None``;
nothing raises. A program that has them but ran nothing under one in the
traced segment reads 0: the cell's requests run in lockstep waves (64
suffix prefills, then 128 decode steps together), so a 3 s segment is often a
wave's decode phase alone and holds no prefill call (PERF.md section 6, PR 33)."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import moe_trace, program_trace, serving_trace

DETAIL_PATHS = (
    ("attn", "latent_down"), ("attn", "latent_up"), ("attn", "absorb"), ("attn", "sdpa"),
    ("attn", "kv_read"), ("attn", "kv_write"), ("moe", "shared"), ("moe", "experts", "all"),
)


def seconds_in(result: Dict[str, Any], path: Sequence[str],
               programs: Optional[Sequence[str]] = None) -> Optional[float]:
    """Device seconds (device 0, the traced window) of the ops whose
    ``op_name`` path holds ``path``'s names as whole parts in that order,
    inside the programs ``programs`` (any, if None)."""
    trace = program_trace.loaded(result)
    if trace is None:
        return None
    dev = trace["devices"][0]
    roots = program_trace.program_scope_by_id(dev.ops)
    lo, hi = trace["window"]
    total = 0.0
    for op in dev.ops:
        if not program_trace._counted(op) or op.end <= lo or op.start >= hi:
            continue
        if programs is not None and roots.get(op.program_id) not in programs:
            continue
        parts = iter(inner for _, inner in program_trace.segments(op.tf_op))
        if all(name in parts for name in path):      # in order: `in` consumes the iterator
            total += op.dur
    return total


def program_names(path: Sequence[str]) -> bool:
    """Whether the program under test names the detail scope ``path`` at all
    (``serving/tracing.py`` ``DETAIL_SCOPES``): where it does, a traced segment
    with no op under it reads 0; where it does not, there is nothing to read."""
    from neuronx_distributed_llama3_2_tpu.serving import tracing

    return path[-1] in getattr(tracing, "DETAIL_SCOPES", {}).get("/".join(path[:-1]), ())


def share(result: Dict[str, Any], path: Sequence[str]) -> Optional[float]:
    """Percent of device busy time under the detail scope ``path``: 0 where
    the traced segment ran nothing under it, None without a device trace or
    where the program does not name the scope."""
    got = moe_trace.path_seconds(result, path)
    if got is None or not program_names(path):
        return None
    return 100.0 * got[0] / got[1]


def no_prefill_in_segment(result: Dict[str, Any]) -> bool:
    """True where the device trace and the engine's dispatch records pair up
    and hold no prefill call: the segment is a wave's decode phase alone."""
    if "mla_no_prefill" not in result:
        kinds, _ = serving_trace.classify(result)
        result["mla_no_prefill"] = bool(kinds and kinds["decode"] and not kinds["prefill"])
        if result["mla_no_prefill"]:
            result.setdefault("notes", []).append(
                f"no prefill call among the traced segment's {len(kinds['decode'])} dispatches "
                "(a wave's decode phase): the prefill metrics read 0")
    return result["mla_no_prefill"]


def add_to_breakdown(result: Dict[str, Any]) -> None:
    """The detail scopes by name in the traced line's ``breakdown``: one
    ``scope <path>`` entry each among ``device_ops`` (seconds, device 0; 0.0
    where the segment ran nothing under a scope), once."""
    reduced = result.get("reduced") or {}
    if "device_ops" not in reduced or result.get("mla_breakdown_done"):
        return
    result["mla_breakdown_done"] = True
    for path in DETAIL_PATHS:
        seconds = seconds_in(result, path)
        if seconds is not None:
            reduced["device_ops"].append(["scope " + "/".join(path), seconds])


def _dispatches(result: Dict[str, Any], kind: str) -> List[dict]:
    prof = result.get("profile") or {}
    return [args for k, args in serving_trace.engine_dispatches(prof.get("engine_steps", [])) if k == kind]


def decode_rows(result: Dict[str, Any]) -> Optional[List[int]]:
    """Cache rows the live lanes of each traced decode dispatch attended over."""
    rows = [int(a["rows"]) for a in _dispatches(result, "decode") if "rows" in a]
    return rows or None


def prefill_calls(result: Dict[str, Any]) -> Optional[List[Tuple[int, int]]]:
    """(bucket, kv_limit — 0 for ``pctx``) of each traced prefill dispatch."""
    calls = [(int(a["bucket"]), int(a["kv_bucket"])) for a in _dispatches(result, "prefill")
             if "bucket" in a and "kv_bucket" in a]
    return calls or None


def routed_with_local(result: Dict[str, Any]) -> Optional[List[Tuple[tuple, int]]]:
    """(routing entry, its live pairs routed to a held expert) of the engine
    steps the profiler saw whole."""
    if result.get("kind") != "serving":
        return None
    tl = program_trace.timeline(result)
    prof = result.get("profile") or {}
    if tl is None or not tl.get("routed") or "routed_local" not in tl or not prof.get("engine_steps"):
        return None
    steps = {s["step"] for s in prof["engine_steps"]}
    rows = [(row, local) for row, local in zip(tl["routed"], tl["routed_local"]) if row[0] in steps]
    return rows or None
