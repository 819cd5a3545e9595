"""The process's set-up recorder (``SETUP``): where a start's seconds went.

One a process and always on, because set-up begins before any engine exists
(the trainer never has one) and is not the hot path: named ``setup.*`` spans
from the process's start to a ready engine, and one event per trace,
lowering and compile that JAX reports (``jax.monitoring``), booked to the
span that was open when it fired — which is what names a serving program,
every one of which is ``jit(fn)`` to JAX. A steady engine traces and compiles
nothing, so it raises no event and opens no span: nothing of this recorder is
in ``step()``, a dispatch or the server's loop, and it has no switch.

This module imports nothing of the package, so every layer can open a span:
``utils/runtime.py`` (``setup.runtime``: the backend's start),
``inference/engine.py`` › ``inference/placement.py``
(``setup.inference_engine`` › ``setup.placement``) and ``serving/engine.py``
(``setup.paged_engine`` › ``setup.prewarm`` › ``setup.program`` with ``key``
and ``kind`` — a catalog key's trace, lowering, compile or cache load and
first dispatch — and ``setup.prewarm``'s siblings ``setup.mark_steady``,
``setup.cost_profiles``, graftmeter's harvest, and ``setup.facts``, a traced
engine's deep harvest). A traced engine's ``timeline()["setup"]`` carries the
record and ``chrome_events()`` draws the spans (``serving/tracing.py``); the
benchmark's ``start-up`` metrics read it (``benchmarks/setup_trace.py``).
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import deque
from typing import Any, Dict, List, Tuple

from jax import monitoring

# jax.monitoring event -> the kind it is recorded under. The first three
# carry seconds (a persistent-cache load is inside ``compile``); the last two
# are counts (seconds 0.0): a compile request that looked in the persistent
# cache, and one that found its program there.
SETUP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_request",
    "/jax/compilation_cache/cache_hits": "cache_hit",
}
SETUP_RECORD_MAX = 1 << 15


def _process_start() -> Tuple[float, bool]:
    """(the instant this process started on ``time.perf_counter()``'s clock,
    False) from ``/proc/self/stat``'s start time against ``CLOCK_BOOTTIME``
    (clock ticks: 10 ms); where that cannot be read, (now, True)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            # the command's name may hold spaces: count fields from its ")"
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return now, True
    return now - age, False


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of ``(start, end)`` intervals: an inner
    ``jit`` reports its trace inside its caller's, and summed they count twice."""
    total, edge = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > edge:
            total += b - max(a, edge)
            edge = b
    return total


class _SetupSpan:
    __slots__ = ("_rec", "_name", "_args", "_index")

    def __init__(self, rec: "SetupRecorder", name: str, args: dict):
        self._rec = rec
        self._name = name
        self._args = args

    def __enter__(self):
        rec = self._rec
        self._index = len(rec.spans)
        parent = rec._open[-1] if rec._open else None
        rec.spans.append([self._name, time.perf_counter(), None, parent, self._args])
        rec._open.append(self._index)
        return self

    def __exit__(self, *exc):
        self._rec.spans[self._index][2] = time.perf_counter()
        self._rec._open.pop()
        return False


class SetupRecorder:
    """``origin`` (the process's start; the recorder's own import instant
    where ``/proc`` cannot say, ``origin_is_import``), ``spans`` — ``[name,
    t0, t1, index of the span that was open or None, args]``, ``t1`` None
    while open — and ``events`` — ``(t_end, kind, seconds, fun_name, index of
    the innermost open span or None)``, kinds as in ``SETUP_EVENTS`` — all on
    ``time.perf_counter()``'s clock. Both are bounded: past
    ``SETUP_RECORD_MAX`` a new span is not recorded and the oldest event is
    dropped. Spans are opened from the one thread that builds the engine; an
    event raised on another thread is booked to that thread's open span."""

    def __init__(self) -> None:
        self.origin, self.origin_is_import = _process_start()
        self.spans: List[list] = []
        self.events: deque = deque(maxlen=SETUP_RECORD_MAX)
        self._open: List[int] = []

    def span(self, name: str, **args: Any):
        """Context manager recording one span under the span that is open."""
        if len(self.spans) >= SETUP_RECORD_MAX:
            return contextlib.nullcontext()
        return _SetupSpan(self, name, args)

    def on_duration(self, event: str, seconds: float, **kw: Any) -> None:
        """``jax.monitoring`` duration listener (and, with 0.0, the counts')."""
        kind = SETUP_EVENTS.get(event)
        if kind is not None:
            self.events.append((
                time.perf_counter(), kind, seconds, kw.get("fun_name"),
                self._open[-1] if self._open else None,
            ))

    def on_event(self, event: str, **kw: Any) -> None:
        self.on_duration(event, 0.0, **kw)

    def record(self) -> dict:
        """``origin``, ``origin_is_import``, ``spans`` and ``events`` as
        plain lists — what ``EngineTracer.timeline()["setup"]`` carries."""
        return {
            "origin": self.origin, "origin_is_import": self.origin_is_import,
            "spans": [list(s) for s in self.spans],
            "events": [list(e) for e in self.events],
        }

    def summary(self) -> Dict[str, float]:
        """What a ready engine logs at INFO: the seconds since the process's
        start (``since_start_s``) and to the backend's (``before_runtime_s``),
        the summed seconds of the closed spans of each name, and what JAX's
        compile path has spent in this process whatever the span —
        ``trace_lower_s`` (the union of the trace intervals plus the
        lowerings: what a warm cache cannot save), ``compile_s`` (a cache
        load is inside one) and ``cache_misses``. The benchmark's ``start-up``
        metrics are computed from :meth:`record`, not from this."""
        out = {"since_start_s": time.perf_counter() - self.origin}
        for name, t0, t1, _parent, _args in self.spans:
            if t1 is not None:
                if name == "setup.runtime":
                    out.setdefault("before_runtime_s", t0 - self.origin)
                out[name] = out.get(name, 0.0) + (t1 - t0)
        took: Dict[str, list] = {}
        for t, kind, seconds, _fun, _span in self.events:
            took.setdefault(kind, []).append((t - seconds, t))
        out["trace_lower_s"] = union_seconds(took.get("trace", [])) + sum(
            b - a for a, b in took.get("lower", ())
        )
        out["compile_s"] = sum(b - a for a, b in took.get("compile", ()))
        out["cache_misses"] = len(took.get("cache_request", ())) - len(took.get("cache_hit", ()))
        return out


# one a process, its listeners registered once, here: they stay registered,
# so a compile a steady engine does pay is on record with its instant
SETUP = SetupRecorder()
monitoring.register_event_duration_secs_listener(SETUP.on_duration)
monitoring.register_event_listener(SETUP.on_event)
