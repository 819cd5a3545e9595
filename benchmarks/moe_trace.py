"""What the OLMoE cell's own per-layer metrics read beside
``program_trace.py``: the tracer's routing counters (``EngineTracer.routed``,
one entry per dispatch of a program with experts) over the traced segment,
device seconds under a scope path that the shared vocabulary does not name,
device seconds of one block inside given programs, and of the copies of expert
weights that the compiler leaves outside every block.

A program without these (the parent of PR 26: no ``routed`` in the timeline,
no ``qk_norm`` or ``selective`` scope) makes every function here return
``None``; nothing raises."""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import program_trace, serving_trace, xplane


def routed_in_trace(result: Dict[str, Any]) -> Optional[List[tuple]]:
    """The routing entries of the engine steps the profiler saw whole:
    ``(step, kind, paths, pairs computed, [tokens per expert])``."""
    if result.get("kind") != "serving":
        return None
    tl = program_trace.timeline(result)
    prof = result.get("profile") or {}
    if tl is None or not tl.get("routed") or not prof.get("engine_steps"):
        return None
    steps = {s["step"] for s in prof["engine_steps"]}
    rows = [row for row in tl["routed"] if row[0] in steps]
    return rows or None


def tokens_per_expert(rows: Sequence[tuple]) -> List[int]:
    return [sum(col) for col in zip(*(row[4] for row in rows))]


def paths_by_kind(rows: Sequence[tuple]) -> Dict[str, set]:
    out: Dict[str, set] = {}
    for _, kind, paths, _, _ in rows:
        out.setdefault(kind, set()).update(paths)
    return out


def block_seconds(result: Dict[str, Any], block: str, programs: Sequence[str]) -> Optional[float]:
    """Device seconds (device 0, the traced window) of the ops under the
    vocabulary scope ``block`` inside the programs ``programs``."""
    shares = program_trace.scope_shares(result)
    if shares is None:
        return None
    return sum(
        sec for (found, _), sec in shares["devices"][0]["groups"].items()
        if block in found and set(programs) & set(found)
    )


def result_types(result: Dict[str, Any]) -> Optional[Dict[Tuple[str, str], str]]:
    """(program id, instruction name) -> the instruction's label with its
    result type (``xplane.op_label``: ``copy bf16[64,1024,2048]``), device 0.
    ``program_trace.Op`` keeps the name alone, so the file is read once more."""
    if "moe_trace_types" not in result:
        out = None
        prof = result.get("profile") or {}
        if program_trace.loaded(result) is not None and prof.get("xplane"):
            plane = next((pl for pl in program_trace.read(prof["xplane"])
                          if re.match(r"^/device:TPU:\d+$", pl.name)), None)
            if plane is not None:
                out = {
                    (str(plane.event_stats.get(mid, {}).get("program_id", "")), xplane.op_name(text)):
                        xplane.op_label(text)
                    for mid, text in plane.event_names.items()
                }
        result["moe_trace_types"] = out
    return result["moe_trace_types"]


def expert_copy_seconds(result: Dict[str, Any], programs: Sequence[str]) -> Optional[Dict[str, float]]:
    """Device seconds (device 0, the traced window), by label, of the
    instructions in ``programs`` that sit under **no** model block — the
    shared readers book them to the program's root — and whose result is an
    array of expert weights: both the hidden size and the expert width among
    its dimensions. That is the layer's expert stack copied out of the layer
    scan and the buffers the selective gather fills; what runs under
    ``moe/experts`` is not here (:func:`block_seconds` has it)."""
    trace, types = program_trace.loaded(result), result_types(result)
    if trace is None or not types:
        return None
    c = result["model_cfg"]
    if not getattr(c, "num_experts", 0):
        return None
    want = {int(c.hidden_size), int(c.intermediate_size)}
    dev = trace["devices"][0]
    roots = program_trace.program_scope_by_id(dev.ops)
    lo, hi = trace["window"]
    out: Dict[str, float] = {}
    for op in dev.ops:
        if not program_trace._counted(op) or op.end <= lo or op.start >= hi:
            continue
        if roots.get(op.program_id) not in programs:
            continue
        if program_trace.block_of(program_trace.scopes_of(op.tf_op)) is not None:
            continue
        label = types.get((op.program_id, op.name), "")
        dims = {int(d) for group in re.findall(r"\[([0-9,]+)\]", label) for d in group.split(",")}
        if want <= dims:
            out[label] = out.get(label, 0.0) + op.dur
    return out


def expert_seconds(result: Dict[str, Any], programs: Sequence[str]) -> Optional[float]:
    """Device seconds the experts cost inside ``programs``: everything under
    ``moe/experts`` and the copies of expert weights outside every block."""
    under, copies = block_seconds(result, "moe/experts", programs), expert_copy_seconds(result, programs)
    if under is None or copies is None:
        return None
    return under + sum(copies.values())


def path_seconds(result: Dict[str, Any], path: Sequence[str]) -> Optional[Tuple[float, float]]:
    """(device seconds of the ops whose ``op_name`` path holds ``path``'s
    names as whole parts in that order, the device's busy seconds), device 0.
    For scopes finer than the shared vocabulary (``attn`` ... ``qk_norm``)."""
    trace = program_trace.loaded(result)
    if trace is None:
        return None
    dev = trace["devices"][0]
    busy = {d["ordinal"]: d["busy_s"] for d in result["reduced"]["devices"]}.get(dev.ordinal)
    if not busy:
        return None
    lo, hi = trace["window"]
    total = 0.0
    for op in dev.ops:
        if not program_trace._counted(op) or op.end <= lo or op.start >= hi:
            continue
        parts = iter(inner for _, inner in program_trace.segments(op.tf_op))
        if all(name in parts for name in path):      # in order: `in` consumes the iterator
            total += op.dur
    return total, busy


def program_calls(result: Dict[str, Any], kinds: Sequence[str]) -> int:
    """Executions of the programs of ``kinds`` in the traced window, by
    ``program_id``."""
    return sum(len(program_trace.program_run_ms(result, kind) or ()) for kind in kinds)


def prefill_bucket(result: Dict[str, Any]) -> Optional[int]:
    """The one prefill bucket the traced steps dispatched; ``None`` where they
    dispatched none or several (a call's token count is then not known from
    its ``program_id`` alone)."""
    prof = result.get("profile") or {}
    buckets = {
        int(args["bucket"])
        for kind, args in serving_trace.engine_dispatches(prof.get("engine_steps", []))
        if kind == "prefill" and "bucket" in args
    }
    return buckets.pop() if len(buckets) == 1 else None
