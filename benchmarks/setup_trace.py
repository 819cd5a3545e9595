"""Reading the program's set-up record: ``utils/setup_record.py`` ``SETUP``, one
a process — ``origin`` (the process's start), ``setup.*`` spans ``[name, t0, t1,
parent index, args]`` and compile-path events ``[t_end, kind, seconds,
fun_name, span index]``, all on ``time.perf_counter()``'s clock. The readers of
the ``start-up`` layer's metrics are one call each into this file, and the phase
arithmetic is here alone: the program's own ``SETUP.summary()`` (its INFO line)
prints seconds by span name and cuts nothing.

``run.py`` reads per-layer metrics in ``--trace 1`` runs only, so every value the
ledger holds is a **traced** start's: a traced engine's programs carry the routing
tap, compile apart from the untraced ones' and trace about twice as much, and its
``setup_s`` is not the judged (untraced) one. ``SETUP`` itself is always on: an
untraced start's table comes from a wrapper that calls these readers (``PERF.md``
§5 **Set-up** has it, and it is the one to budget a ``perf_opt`` against).

Set-up's end for a reader is ``origin + result["setup_s"]`` (``run.py`` takes
its own ``T_PROCESS`` a few tens of milliseconds after ``origin``, so the cut
falls that much before the instant ``setup_s`` was taken): only spans and
events that ended by then count. The training cell has no tracer and a process
may build more than one engine, so the record is taken from ``SETUP`` itself
and spans of one name are summed. Where the program has no recorder (the
parent's) every reader returns ``None``: nothing to read, left out.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

try:
    from neuronx_distributed_llama3_2_tpu.utils import setup_record
except ImportError:          # a program from before the recorder
    setup_record = None

ENGINES = ("setup.inference_engine", "setup.paged_engine")
# the children of ``setup.paged_engine`` that are phases of their own
PHASES = ("setup.prewarm", "setup.cost_profiles", "setup.facts")


def record(result: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The set-up record cut at set-up's end — ``origin``, ``end``, ``spans``
    (closed by ``end``, each with its index in the program's list first) and
    ``events`` — or ``None``. A result that brings a ``setup_record`` of its
    own (a test's hand-written one) is read in place of the program's."""
    if "setup_cut" in result:
        return result["setup_cut"]
    raw = result.get("setup_record")
    if raw is None and setup_record is not None:
        raw = setup_record.SETUP.record()
    out = None
    if raw is not None:
        end = raw["origin"] + result["setup_s"]
        out = {
            "origin": raw["origin"], "end": end,
            "spans": [
                (i, *s) for i, s in enumerate(raw["spans"]) if s[2] is not None and s[2] <= end
            ],
            "events": [e for e in raw["events"] if e[0] <= end],
            # nothing opens a span inside it: its events carry its own index
            "facts": {i for i, s in enumerate(raw["spans"]) if s[0] == "setup.facts"},
        }
    result["setup_cut"] = out
    return out


def span_seconds(result, *names: str, child_of: Tuple[str, ...] = ()) -> Optional[float]:
    """Summed seconds of the spans called one of ``names`` (``child_of``: only
    those opened directly under a span of one of these names); ``None`` where
    there is no record or no such span."""
    rec = record(result)
    if rec is None:
        return None
    name_of = {i: name for i, name, *_ in rec["spans"]}
    took = [
        t1 - t0 for _i, name, t0, t1, parent, _args in rec["spans"]
        if name in names and (not child_of or name_of.get(parent) in child_of)
    ]
    return sum(took) if took else None


def _runtime(rec) -> Optional[tuple]:
    return next((s for s in rec["spans"] if s[1] == "setup.runtime"), None)


def before_runtime_s(result) -> Optional[float]:
    """``origin`` → ``setup.runtime`` opens: the interpreter, ``import jax``,
    the package's and the caller's imports."""
    rec = record(result)
    runtime = _runtime(rec) if rec else None
    return None if runtime is None else runtime[2] - rec["origin"]


def engine_build_s(result) -> Optional[float]:
    """The engines' construction less the three phases with metrics of their
    own: placement, pool, residents, catalog, the freeze."""
    whole = span_seconds(result, *ENGINES)
    if whole is None:
        return None
    return whole - (span_seconds(result, *PHASES, child_of=ENGINES) or 0.0)


def outside_spans_s(result) -> Optional[float]:
    """Set-up's seconds after ``setup.runtime`` under no span of the program:
    the caller's (weights from the seed, the correctness check, imports)."""
    rec = record(result)
    runtime = _runtime(rec) if rec else None
    if runtime is None:
        return None
    after = runtime[3]
    roots = sum(
        t1 - t0 for _i, _name, t0, t1, parent, _args in rec["spans"]
        if parent is None and t0 >= after
    )
    return (rec["end"] - after) - roots


def _intervals(result, kind: str, span: Optional[int] = None) -> Optional[List[Tuple[float, float]]]:
    """(start, end) of the events of ``kind`` — of span ``span`` alone, else of
    the whole set-up but for what fired under ``setup.facts`` (the traced
    engine's deep harvest: a traced run's own cost, not a start's)."""
    rec = record(result)
    if rec is None:
        return None
    return [
        (t - secs, t) for t, k, secs, _fun, at in rec["events"]
        if k == kind and (at == span if span is not None else at not in rec["facts"])
    ]


def trace_lower_s(result, span: Optional[int] = None) -> Optional[float]:
    """What a warm cache cannot save: the union of the trace events' intervals
    (an inner ``jit`` reports inside its caller's trace: summed they would
    count twice) plus the lowerings (which do not nest)."""
    traces = _intervals(result, "trace", span)
    if traces is None:
        return None
    return setup_record.union_seconds(traces) + sum(b - a for a, b in _intervals(result, "lower", span))


def compile_s(result, span: Optional[int] = None) -> Optional[float]:
    """Seconds in ``backend_compile``: a compile, or the cache's load in its place."""
    took = _intervals(result, "compile", span)
    return None if took is None else sum(b - a for a, b in took)


def cache_misses(result, span: Optional[int] = None) -> Optional[int]:
    """Compile requests that looked in the persistent cache less those it
    held: 0 or 1 (the placement's relayout is never cached) says a warm start."""
    asked = _intervals(result, "cache_request", span)
    return None if asked is None else len(asked) - len(_intervals(result, "cache_hit", span))


def costliest_programs(result, n: int = 5) -> List[str]:
    """The ``n`` ``setup.program`` spans that took longest, as text: key,
    seconds, trace + lower / compile seconds, cache hit or miss."""
    rec = record(result)
    if rec is None:
        return []
    programs = sorted(
        (s for s in rec["spans"] if s[1] == "setup.program"), key=lambda s: s[2] - s[3]
    )[:n]
    lines = []
    for i, _name, t0, t1, _parent, args in programs:
        asked = len(_intervals(result, "cache_request", i))
        cached = "no compile" if not asked else "miss" if cache_misses(result, i) else "hit"
        key = re.sub(r"\w+Config\([^)]*\)", "cfg", str(args.get("key")))
        lines.append(
            f"{key} {t1 - t0:.2f} s (trace + lower {trace_lower_s(result, i):.2f}, "
            f"compile {compile_s(result, i):.2f}, {cached})"
        )
    return lines
