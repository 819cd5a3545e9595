"""From the profiler's ``.xplane.pb`` to numbers: busy and idle time per
device, time per XLA module and per named op or kernel, collective time and its
exposed part, the operations that took most time, and the long idle gaps with
what the host was doing in them. Needs nothing but JAX
(``jax.profiler.ProfileData``).

What a v5e trace looks like (``tools/record_tiny_trace.py`` prints one): each
chip is a plane ``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<fingerprint>)``), a line ``XLA Ops`` (one
event per HLO instruction executed, named by the instruction's text
``%name = ...`` — a Pallas kernel shows as ``%<kernel name>[.n] = ...
custom-call``) and a line ``Async XLA Ops`` (the start-to-done span of
asynchronous copies and collectives). The host is the plane ``/host:CPU``, one
line per thread; ``jax.profiler.TraceAnnotation`` spans land on the thread that
made them. Device and host clocks agree to about a millisecond.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]   # seconds

COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE = re.compile(r"^(?P<name>.*?)\((?P<id>\d+)\)$")
_OP = re.compile(r"^%(?P<name>[^\s=]+)\s*=\s*(?P<type>\(?[a-z0-9]+\[[0-9,]*\])?")
# ops that only contain other ops: their time is their children's
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # seconds on the trace's clock
    dur: float
    label: str = ""   # ops: base name and result type, for the breakdown

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    modules: List[Event]        # name is "jit_fn(1234)"
    ops: List[Event]            # name is the HLO instruction's name
    async_ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: Dict[str, List[Event]]   # thread line -> events


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the union ``a`` not covered by the union ``b`` (both
    sorted and disjoint, as :func:`union` returns them)."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP.match(text)
    return m.group("name") if m else text.split(" ", 1)[0]


def base_name(name: str) -> str:
    """``flash_fwd.3`` -> ``flash_fwd``: XLA numbers repeated instructions."""
    return re.sub(r"(\.\d+)+$", "", name)


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVE_PREFIXES)


def is_marker(name: str) -> bool:
    """The start/done halves of an asynchronous op: the work is the span on
    the ``Async XLA Ops`` line, the halves mark issue and wait."""
    b = base_name(name)
    return b.endswith("-start") or b.endswith("-done")


def op_label(text: str) -> str:
    """``%fusion.12 = bf16[8,128]{1,0} fusion(...)`` -> ``fusion bf16[8,128]``:
    XLA's numbering changes from compile to compile, the result type says
    which of the many ``fusion`` ops this is."""
    m = _OP.match(text)
    if not m:
        return base_name(op_name(text))
    return (base_name(m.group("name")) + " " + (m.group("type") or "").lstrip("(")).strip()


def _events(line, ops: bool = False) -> List[Event]:
    return [
        Event(op_name(ev.name) if ops else ev.name, ev.start_ns * 1e-9,
              ev.duration_ns * 1e-9, op_label(ev.name) if ops else "")
        for ev in line.events
    ]


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append(DeviceTrace(
                ordinal=int(m.group(1)),
                modules=_events(lines["XLA Modules"]) if "XLA Modules" in lines else [],
                ops=_events(lines["XLA Ops"], ops=True) if "XLA Ops" in lines else [],
                async_ops=_events(lines["Async XLA Ops"], ops=True) if "Async XLA Ops" in lines else [],
            ))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                # every Python thread's line is called "python3": keep them apart
                host[f"{line.name}#{len(host)}"] = _events(line)
    devices.sort(key=lambda d: d.ordinal)
    return Trace(devices=devices, host=host)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def module_key(name: str) -> Tuple[str, str]:
    m = _MODULE.match(name)
    return (m.group("name"), m.group("id")) if m else (name, "")


def reduce_device(dev: DeviceTrace, lo: float, hi: float) -> Dict:
    ops = [
        e for e in dev.ops
        if e.end > lo and e.start < hi and base_name(e.name) not in CONTAINERS
    ]
    busy = union(clip(((e.start, e.end) for e in ops), lo, hi))
    compute = union(clip(
        ((e.start, e.end) for e in ops if not is_collective(e.name) and not is_marker(e.name)),
        lo, hi,
    ))
    collective = union(clip(
        [(e.start, e.end) for e in ops if is_collective(e.name)]
        + [(e.start, e.end) for e in dev.async_ops if is_collective(e.name)],
        lo, hi,
    ))
    steps = union(clip(((e.start, e.end) for e in dev.modules), lo, hi))
    by_op: Dict[str, List[float]] = {}
    by_label: Dict[str, float] = {}
    for e in ops:
        if is_marker(e.name) and not is_collective(e.name):
            continue
        by_op.setdefault(base_name(e.name), []).append(e.dur)
        by_label[e.label] = by_label.get(e.label, 0.0) + e.dur
    by_module: Dict[str, List[float]] = {}
    for e in dev.modules:
        if e.end > lo and e.start < hi:
            by_module.setdefault(e.name, []).append(e.dur)
    in_steps_idle = subtract(steps, compute)
    return {
        "ordinal": dev.ordinal,
        "busy": busy, "busy_s": total(busy), "compute_s": total(compute),
        "module_s": total(steps),
        "collective_s": total(collective),
        "collective_exposed_s": total(subtract(collective, compute)),
        "no_compute_in_modules_s": total(in_steps_idle),
        "ops": {k: (len(v), sum(v)) for k, v in by_op.items()},
        "by_label": by_label,
        "modules": by_module,
    }


def annotation_at(host: Dict[str, List[Event]], names: Sequence[str], t: float) -> str:
    """The innermost of the benchmark's host spans that covers instant ``t``,
    else the longest host event of a thread that made such spans, else
    ``"unattributed"``."""
    best: Optional[Event] = None
    fallback: Optional[Event] = None
    for events in host.values():
        if not any(e.name in names for e in events):
            continue
        for e in events:
            if e.start <= t < e.end:
                if e.name in names:
                    if best is None or e.dur < best.dur:
                        best = e
                elif fallback is None or e.dur > fallback.dur:
                    fallback = e
    if best is not None:
        return best.name
    return f"host:{fallback.name}" if fallback is not None else "unattributed"


def reduce(path: str, annotations: Sequence[str] = (), window: Optional[Interval] = None,
           min_gap_s: float = 50e-6, top: int = 10) -> Dict:
    """The whole reduction. ``window`` (seconds on the trace's clock) defaults
    to the extent of the device events."""
    trace = load(path)
    if not trace.devices or not any(d.ops for d in trace.devices):
        return {"devices": [], "busy_s": 0.0, "window_s": 0.0}
    if window is None:
        starts = [e.start for d in trace.devices for e in d.ops + d.modules]
        ends = [e.end for d in trace.devices for e in d.ops + d.modules]
        window = (min(starts), max(ends))
    lo, hi = window
    devs = [reduce_device(d, lo, hi) for d in trace.devices]
    window_s = hi - lo
    worst = max(devs, key=lambda d: window_s - d["busy_s"])
    ops: Dict[str, float] = {}
    for d in devs:
        for name, seconds in d["by_label"].items():
            ops[name] = ops.get(name, 0.0) + seconds / len(devs)
    gap_by: Dict[str, float] = {}
    for a, b in gaps(worst["busy"], lo, hi):
        if b - a >= min_gap_s:
            who = annotation_at(trace.host, annotations, (a + b) / 2)
            gap_by[who] = gap_by.get(who, 0.0) + (b - a)
    ranked = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devs) / len(devs),
        "idle_share_worst": 1.0 - worst["busy_s"] / window_s,
        "devices": devs,
        "device_ops": ranked(ops),
        "idle_gaps": ranked(gap_by),
        "module_runs": [
            (e.name, e.start, e.dur) for e in trace.devices[0].modules
            if e.end > lo and e.start < hi
        ],
    }
