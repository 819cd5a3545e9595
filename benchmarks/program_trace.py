"""One timeline: the program's own spans joined to the device trace, and the
named scopes of the device work.

Three readings the per-layer metrics of PR 23 share, each worked out once per
result:

- **the metadata of a trace** (:func:`read`). ``jax.profiler.ProfileData``
  shows an event's name, start and duration and hides the plane's
  ``event_metadata``, which holds, per HLO instruction, ``tf_op`` (the JAX
  ``op_name`` path — ``jax.named_scope`` names are segments of it),
  ``program_id`` (the fingerprint in the module event's name
  ``jit_fn(<id>)``), ``hlo_category``, ``flops`` and ``bytes_accessed``. This
  walks the wire format of ``XSpace`` by hand (``xplane.proto``: field numbers
  below), so it needs neither TensorFlow nor protobuf. ``program_id`` joins a
  device module run to the program that ran, exactly: nothing is paired by
  order;
- **the clock join** (:func:`clock_join`). The program's tracer
  (``serving/tracing.py``) stamps ``time.perf_counter()``; while a profile is
  taken every traced step is also a ``graft.step`` annotation on the profile's
  host plane, carrying its ``step`` index. The offset between the clocks is the
  median, over the steps both sides saw, of (annotation start − the step
  record's ``t0``); the spread of the residuals is the join's error;
- **scope shares** (:func:`scope_shares`): device time under each named scope
  over device busy time, what could not be placed, and forward / recompute /
  backward from the transform segments of ``tf_op``.

A program that has none of this (the parent of PR 23: no scopes, no
``graft.step``, no ``timeline()``) makes every function here return ``None``
or an empty reading; nothing raises.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import struct
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks import stats, xplane

# The program's vocabulary of scopes (``serving/tracing.py`` ``SCOPES``; a test
# holds the two equal). A child counts only under its parent.
PROGRAM_SCOPES = ("pctx", "psfx", "pdecode", "train_step")
BLOCK_SCOPES = (
    "embed", "norm", "attn", "mlp", "moe", "lm_head", "ce", "sample",
    "grad_clip", "optimizer",
)
CHILD_SCOPES = {
    "attn": ("qkv", "rope", "kv_write", "kv_read", "sdpa", "o_proj"),
    "moe": ("router", "experts"),
}
SCOPES = PROGRAM_SCOPES + BLOCK_SCOPES + tuple(
    f"{parent}/{child}" for parent, children in CHILD_SCOPES.items() for child in children
)
# the blocks of a model's forward: only their ops have a forward/backward phase
MODEL_SCOPES = ("embed", "norm", "attn", "mlp", "moe", "lm_head", "ce")
STEP_ANNOTATION = "graft.step"
# the tracer's events that are a step's children for self time (guide, section 4)
STEP_CHILDREN = ("dispatch", "prefill", "prefill_chunk", "readback")
# a program whose blocks run outside any jvp for at least this share of their
# linearised time replays them (manual VJP); under it, the linearisation is
# the one forward
REPLAY_MIN_PRIMAL = 0.25
# device and host clocks of one trace agree to about this (xplane.py)
CLOCK_AGREEMENT_S = 1e-3


# ---------------------------------------------------------------------------
# the wire format of XSpace
# ---------------------------------------------------------------------------
# XSpace: planes=1. XPlane: name=2, lines=3, event_metadata=4 (map), stat_metadata=5
# (map). XLine: name=2, timestamp_ns=3, events=4. XEvent: metadata_id=1,
# offset_ps=2, duration_ps=3, stats=4. XStat: metadata_id=1, double=2, uint64=3,
# int64=4, str=5, bytes=6, ref=7. XEventMetadata: id=1, name=2, stats=5.
# XStatMetadata: id=1, name=2. A map entry: key=1, value=2.

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in ``buf``."""
    while i < end:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
    name, value = None, None
    for field, _, v in _fields(buf, *span):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = buf[v[0]:v[1]].decode("utf-8", "replace")
        elif field == 6:
            value = buf[v[0]:v[1]]
        elif field == 7:
            value = stat_names.get(v, str(v))
    return name, value


@dataclasses.dataclass
class Line:
    name: str
    timestamp_ns: int
    # (metadata id, start in seconds, duration in seconds, the stats' byte spans)
    events: List[Tuple[int, float, float, tuple]]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    event_names: Dict[int, str]
    event_stats: Dict[int, Dict[str, Any]]   # metadata id -> {stat name: value}
    stat_names: Dict[int, str]
    buf: bytes

    def stats_of(self, event) -> Dict[str, Any]:
        """An event's own stats (``step`` of a host annotation)."""
        return dict(_stat(self.buf, span, self.stat_names) for span in event[3])


def _map_value(buf: bytes, span: Tuple[int, int]) -> Optional[Tuple[int, int]]:
    for field, _, v in _fields(buf, *span):
        if field == 2:
            return v
    return None


def _plane(buf: bytes, span: Tuple[int, int], wanted) -> Optional[Plane]:
    name, lines, ev_meta, stat_meta = "", [], [], []
    for field, _, v in _fields(buf, *span):
        if field == 2:
            name = buf[v[0]:v[1]].decode()
        elif field == 3:
            lines.append(v)
        elif field == 4:
            ev_meta.append(v)
        elif field == 5:
            stat_meta.append(v)
    if not wanted(name):
        return None
    stat_names: Dict[int, str] = {}
    for entry in stat_meta:
        value = _map_value(buf, entry)
        if value is None:
            continue
        sid, sname = 0, ""
        for field, _, v in _fields(buf, *value):
            if field == 1:
                sid = v
            elif field == 2:
                sname = buf[v[0]:v[1]].decode()
        stat_names[sid] = sname
    event_names: Dict[int, str] = {}
    event_stats: Dict[int, Dict[str, Any]] = {}
    for entry in ev_meta:
        value = _map_value(buf, entry)
        if value is None:
            continue
        mid, mname, mstats = 0, "", {}
        for field, _, v in _fields(buf, *value):
            if field == 1:
                mid = v
            elif field == 2:
                mname = buf[v[0]:v[1]].decode("utf-8", "replace")
            elif field == 5:
                k, val = _stat(buf, v, stat_names)
                mstats[k] = val
        event_names[mid] = mname
        event_stats[mid] = mstats
    out_lines = []
    for lspan in lines:
        lname, ts_ns, events = "", 0, []
        for field, _, v in _fields(buf, *lspan):
            if field == 2:
                lname = buf[v[0]:v[1]].decode()
            elif field == 3:
                ts_ns = _signed(v)
            elif field == 4:
                events.append(v)
        parsed = []
        for espan in events:
            mid = off_ps = dur_ps = 0
            st = []
            for field, _, v in _fields(buf, *espan):
                if field == 1:
                    mid = v
                elif field == 2:
                    off_ps = _signed(v)
                elif field == 3:
                    dur_ps = _signed(v)
                elif field == 4:
                    st.append(v)
            parsed.append((mid, ts_ns * 1e-9 + off_ps * 1e-12, dur_ps * 1e-12, tuple(st)))
        out_lines.append(Line(lname, ts_ns, parsed))
    return Plane(name, out_lines, event_names, event_stats, stat_names, buf)


def read(path: str, wanted=lambda name: name.startswith("/device:TPU:") or name == "/host:CPU") -> List[Plane]:
    """The planes of an ``.xplane.pb`` whose name ``wanted`` accepts, with
    their metadata."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for field, _, v in _fields(buf, 0, len(buf)):
        if field == 1:
            plane = _plane(buf, v, wanted)
            if plane is not None:
                planes.append(plane)
    return planes


# ---------------------------------------------------------------------------
# what the metrics read from the planes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # the HLO instruction's name, `fusion.12`
    start: float
    dur: float
    tf_op: str         # "" where the compiler made the instruction itself
    program_id: str
    category: str

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Device:
    ordinal: int
    modules: List[Tuple[str, str, float, float]]   # (name, program id, start, duration)
    ops: List[Op]


def devices_of(planes: Sequence[Plane]) -> List[Device]:
    out = []
    for plane in planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for mid, start, dur, _ in line.events:
                    name = plane.event_names.get(mid, "")
                    modules.append((name, xplane.module_key(name)[1], start, dur))
            elif line.name == "XLA Ops":
                for mid, start, dur, _ in line.events:
                    meta = plane.event_stats.get(mid, {})
                    ops.append(Op(
                        xplane.op_name(plane.event_names.get(mid, "")), start, dur,
                        str(meta.get("tf_op", "") or ""), str(meta.get("program_id", "")),
                        str(meta.get("hlo_category", "") or ""),
                    ))
        out.append(Device(int(m.group(1)), modules, ops))
    out.sort(key=lambda d: d.ordinal)
    return out


def annotations_of(planes: Sequence[Plane], name: str, stat: str = "step") -> List[Tuple[int, float, float]]:
    """(``stat``'s value, start, end) of every host annotation called ``name``."""
    out = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        ids = {mid for mid, n in plane.event_names.items() if n == name}
        if not ids:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev[0] in ids:
                    value = plane.stats_of(ev).get(stat)
                    if value is not None:
                        out.append((int(value), ev[1], ev[1] + ev[2]))
    out.sort(key=lambda a: a[1])
    return out


# ---------------------------------------------------------------------------
# the clock join
# ---------------------------------------------------------------------------

def clock_join(annotations: Iterable[Tuple[int, float, float]],
               steps: Iterable[dict]) -> Optional[Dict[str, Any]]:
    """Offset (seconds to add to a ``perf_counter`` time to put it on the
    trace's clock) from the steps both sides saw, joined by ``step`` index —
    never by order. ``error_us`` is the 90th percentile of the residuals'
    magnitude."""
    t0 = {s["step"]: s["t0"] for s in steps}
    deltas = [start - t0[step] for step, start, _ in annotations if step in t0]
    if not deltas:
        return None
    offset = stats.median(deltas)
    residuals = sorted(abs(d - offset) for d in deltas)
    return {
        "offset": offset, "steps": len(deltas),
        "error_us": 1e6 * residuals[min(len(residuals) - 1, int(0.9 * len(residuals)))],
        "max_error_us": 1e6 * residuals[-1],
    }


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

_WRAPPED = re.compile(r"^((?:(?:transpose|jvp|vmap)\()*)([^()]*)\)*$")


def segments(tf_op: str) -> List[Tuple[str, str]]:
    """(wrappers, inner) of each part of an ``op_name`` path:
    ``transpose(jvp(attn))`` -> (``transpose(jvp(``, ``attn``). The last part
    is the primitive (``dot_general:``), never a scope."""
    out = []
    for part in tf_op.rstrip(":").split("/")[:-1]:
        m = _WRAPPED.match(part)
        out.append((m.group(1), m.group(2)) if m else ("", part))
    return out


@functools.lru_cache(maxsize=None)      # a trace repeats each instruction's path every loop turn
def scopes_of(tf_op: str) -> Tuple[str, ...]:
    """The vocabulary's scopes on the path, whole parts only (``norm`` never
    matches ``attn_norm``), a child only after its parent."""
    found, open_parents = [], set()
    for _, inner in segments(tf_op):
        if inner in PROGRAM_SCOPES or inner in BLOCK_SCOPES:
            if inner not in found:
                found.append(inner)
            if inner in CHILD_SCOPES:
                open_parents.add(inner)
        else:
            for parent in open_parents:
                if inner in CHILD_SCOPES[parent] and f"{parent}/{inner}" not in found:
                    found.append(f"{parent}/{inner}")
    return tuple(found)


@functools.lru_cache(maxsize=None)
def phase_of(tf_op: str) -> str:
    """``recompute``: under the remat wrapper's ``rematted_computation``;
    ``backward``: under a ``transpose(...)`` part; ``replay``: under a
    ``jvp(...)`` part only (a linearisation: the forward of plain autodiff, or
    a manual VJP running a stage again — :func:`scope_shares` tells which);
    else ``forward``."""
    parts = tf_op.rstrip(":").split("/")
    if "rematted_computation" in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if any(p.startswith("jvp(") for p in parts):
        return "replay"
    return "forward"


def program_scope_by_id(ops: Iterable[Op]) -> Dict[str, str]:
    """program id -> the program scope its instructions carry (one)."""
    seen: Dict[str, set] = {}
    for op in ops:
        for scope in scopes_of(op.tf_op):
            if scope in PROGRAM_SCOPES:
                seen.setdefault(op.program_id, set()).add(scope)
    return {pid: next(iter(s)) for pid, s in seen.items() if len(s) == 1}


def _counted(op: Op) -> bool:
    """The ops ``xplane.reduce_device`` counts into busy time and labels."""
    base = xplane.base_name(op.name)
    if base in xplane.CONTAINERS:
        return False
    return not (xplane.is_marker(op.name) and not xplane.is_collective(op.name))


def device_scope_seconds(dev: Device, lo: float, hi: float) -> Dict[str, Any]:
    """Seconds of one device's ops inside [lo, hi): ``groups`` maps (the
    scopes on the op's path, its phase) to seconds, ``unscoped_s`` is what has
    no scope at all. The phase is ``""`` outside the model's blocks."""
    roots = program_scope_by_id(dev.ops)
    groups: Dict[Tuple[Tuple[str, ...], str], float] = {}
    unscoped = 0.0
    for op in dev.ops:
        if not _counted(op) or op.end <= lo or op.start >= hi:
            continue
        found = scopes_of(op.tf_op)
        root = roots.get(op.program_id)
        if root and root not in found:
            # an instruction the compiler made (a copy, a fusion across two
            # scopes) is booked to its program's root
            found = (root,) + found
        if not found:
            unscoped += op.dur
            continue
        phase = phase_of(op.tf_op) if any(s in MODEL_SCOPES for s in found) else ""
        key = (found, phase)
        groups[key] = groups.get(key, 0.0) + op.dur
    return {"groups": groups, "unscoped_s": unscoped}


def block_of(found: Sequence[str]) -> Optional[str]:
    """The outermost model block on an op's path (the head's norm belongs to
    ``ce``)."""
    return next((s for s in found if s in MODEL_SCOPES), None)


def replayed_blocks(device: Dict[str, Any]) -> set:
    """The blocks whose linearisation (``replay``) is a second run: those the
    program also runs outside any jvp for a comparable time (a manual VJP that
    replays a stage from its stashed input runs both passes at the same cost).
    Under plain autodiff the linearisation is the one forward, and all that is
    left outside it are the few loop-invariant ops XLA hoists (rope tables,
    masks) — as for the head under 1F1B, which only ever runs inside its VJP."""
    primal: Dict[str, float] = {}
    replay: Dict[str, float] = {}
    for (found, phase), sec in device["groups"].items():
        block = block_of(found)
        if block and phase in ("forward", "replay"):
            into = primal if phase == "forward" else replay
            into[block] = into.get(block, 0.0) + sec
    return {b for b, r in replay.items() if primal.get(b, 0.0) >= REPLAY_MIN_PRIMAL * r}


def recompute_seconds(device: Dict[str, Any]) -> float:
    """Seconds of the model's blocks run again: under the remat wrapper's
    ``rematted_computation``, or the replay of a block in
    :func:`replayed_blocks`."""
    again = replayed_blocks(device)
    return sum(
        sec for (found, phase), sec in device["groups"].items()
        if phase == "recompute" or (phase == "replay" and block_of(found) in again)
    )


def seconds_under(device: Dict[str, Any], any_of: Optional[Sequence[str]] = None,
                  phases: Optional[Sequence[str]] = None) -> float:
    """Seconds of the ops under at least one of ``any_of`` (every scoped op
    if ``None``) whose phase is in ``phases`` (any if ``None``); an op is
    counted once however many of the scopes it sits under."""
    return sum(
        sec for (found, phase), sec in device["groups"].items()
        if (any_of is None or set(any_of) & set(found)) and (phases is None or phase in phases)
    )


# ---------------------------------------------------------------------------
# per result, worked out once
# ---------------------------------------------------------------------------

def _mean(xs: Sequence[float]) -> float:
    return sum(xs) / len(xs)


def _note(result: Dict[str, Any], text: str) -> None:
    notes = result.setdefault("notes", [])
    if text not in notes:
        notes.append(text)


def loaded(result: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The trace of this result read with its metadata: devices, the window
    ``xplane.reduce`` used, the ``graft.step`` annotations."""
    if "program_trace" in result:
        return result["program_trace"]
    out = None
    prof, reduced = result.get("profile"), result.get("reduced")
    if prof and prof.get("xplane") and reduced and reduced.get("devices"):
        t = time.perf_counter()
        planes = read(prof["xplane"])
        devices = devices_of(planes)
        if devices and any(d.ops for d in devices):
            starts = [o.start for d in devices for o in d.ops] + [m[2] for d in devices for m in d.modules]
            ends = [o.end for d in devices for o in d.ops] + [m[2] + m[3] for d in devices for m in d.modules]
            out = {
                "devices": devices, "window": (min(starts), max(ends)),
                "steps": annotations_of(planes, STEP_ANNOTATION),
            }
            _note(result, f"program_trace: read {prof['xplane'].rsplit('/', 1)[-1]} with its "
                          f"metadata in {time.perf_counter() - t:.1f} s")
    result["program_trace"] = out
    return out


def scope_shares(result: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Per device, seconds by scope beside ``xplane.reduce``'s busy seconds;
    ``None`` where the trace names no scope of the vocabulary. The note says
    what could not be placed."""
    if "scope_shares" in result:
        return result["scope_shares"]
    out = None
    trace = loaded(result)
    if trace is not None:
        lo, hi = trace["window"]
        busy = {d["ordinal"]: d["busy_s"] for d in result["reduced"]["devices"]}
        per_device = [
            {"busy_s": busy[dev.ordinal], **device_scope_seconds(dev, lo, hi)}
            for dev in trace["devices"] if busy.get(dev.ordinal)
        ]
        if per_device and any(d["groups"] for d in per_device):
            out = {"devices": per_device}
            unscoped = _mean([100.0 * d["unscoped_s"] / d["busy_s"] for d in per_device])
            names = {s for d in per_device for found, _ in d["groups"] for s in found}
            ranked = sorted(((n, mean_share(out, (n,))) for n in names), key=lambda kv: -kv[1])
            _note(result, f"scopes (% of busy time, mean over {len(per_device)} devices): unscoped "
                          f"{unscoped:.2f}; " + ", ".join(f"{k} {v:.1f}" for k, v in ranked[:16]))
            alone = _mean([100.0 * sum(sec for (found, _), sec in d["groups"].items()
                                       if len(found) == 1 and found[0] in PROGRAM_SCOPES) / d["busy_s"]
                           for d in per_device])
            _note(result, f"scopes: {alone:.1f} % of busy time sits under a program's root alone (the layer "
                          f"scan's slices and copies, instructions the compiler made, fusions across scopes)")
            phases = {ph: mean_share(out, None, (ph,)) for ph in ("forward", "replay", "recompute", "backward")}
            if phases["backward"]:
                again = sorted(replayed_blocks(per_device[0]))
                _note(result, "phases of the model's blocks (% of busy time): " + ", ".join(
                    f"{k} {v:.1f}" for k, v in phases.items())
                    + "; replay = the pass under a jvp: a second run of " + (", ".join(again) or "nothing")
                    + " (they also run outside it), the one forward of the rest")
    result["scope_shares"] = out
    return out


def mean_share(shares: Dict[str, Any], any_of: Optional[Sequence[str]],
               phases: Optional[Sequence[str]] = None) -> float:
    """Mean over devices of :func:`seconds_under` over busy seconds, percent."""
    return _mean([100.0 * seconds_under(d, any_of, phases) / d["busy_s"] for d in shares["devices"]])


# ---------------------------------------------------------------------------
# the tracer's timeline (host spans of the program, perf_counter clock)
# ---------------------------------------------------------------------------

def timeline(result: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``EngineTracer.timeline()`` of a serving result, with its requests
    indexed by rid; ``None`` for a program without it or with tracing off."""
    if "timeline" in result:
        return result["timeline"]
    out = None
    tracer = getattr(result.get("serving"), "tracer", None)
    take = getattr(tracer, "timeline", None)
    if take is not None and getattr(tracer, "enabled", False):
        out = take()
        out["first_token"] = {rid: ts for name, ts, rid, _ in out["marks"] if name == "first_token"}
    result["timeline"] = out
    return out


def window_requests(result: Dict[str, Any]) -> List[dict]:
    """Front-door roots of the requests accepted inside the measured window."""
    tl = timeline(result)
    if tl is None:
        return []
    lo, hi = result["window"]
    return [d for d in tl["requests"] if d["rid"] is not None and lo <= d["t0"] < hi]


def door_span(door: dict, name: str) -> Optional[Tuple[float, float]]:
    for n, t0, t1 in door["spans"]:
        if n == name:
            return t0, t1
    return None


def state_start(tl: Dict[str, Any], rid: int, state: str) -> Optional[float]:
    for ts, st in tl["states"].get(rid, ()):
        if st == state:
            return ts
    return None


def step_self_ms(step: dict) -> float:
    """A step's self time: its duration less the part its ``dispatch`` /
    ``prefill*`` / ``readback`` children cover."""
    children = xplane.union(
        (t0, t1) for ph, name, t0, t1, _ in step["events"] if ph == "X" and name in STEP_CHILDREN
    )
    return 1e3 * ((step["t1"] - step["t0"]) - xplane.total(xplane.clip(children, step["t0"], step["t1"])))


def ttft_legs(result: Dict[str, Any]) -> Optional[Dict[str, List[float]]]:
    """Per request accepted in the window, the in-program legs of TTFT in ms:
    ``pre_submit`` (accepted -> ``door.submit`` end), ``queue`` (``queued`` ->
    ``prefilling``), ``admit_to_first_token``, ``first_write`` (``first_token``
    mark -> ``door.first_write`` end) and their ``total`` (accepted -> first
    chunk written)."""
    if "ttft_legs" in result:
        return result["ttft_legs"]
    tl = timeline(result)
    legs: Dict[str, List[float]] = {
        k: [] for k in ("pre_submit", "queue", "admit_to_first_token", "first_write", "total")
    }
    for door in window_requests(result):
        rid = door["rid"]
        submit, write = door_span(door, "door.submit"), door_span(door, "door.first_write")
        queued, admitted = state_start(tl, rid, "queued"), state_start(tl, rid, "prefilling")
        first = tl["first_token"].get(rid)
        if None in (submit, write, queued, admitted, first):
            continue
        legs["pre_submit"].append(1e3 * (submit[1] - door["t0"]))
        legs["queue"].append(1e3 * (admitted - queued))
        legs["admit_to_first_token"].append(1e3 * (first - admitted))
        legs["first_write"].append(1e3 * (write[1] - first))
        legs["total"].append(1e3 * (write[1] - door["t0"]))
    out = legs if legs["total"] else None
    if out is not None:
        from benchmarks import serving

        client = stats.median(serving.ttft_ms(result.get("in_window", []))) or float("nan")
        late = stats.median([
            (s.sent - s.due) * 1e3 for s in result.get("in_window", []) if s.sent is not None
        ]) or float("nan")
        med = {k: stats.median(v) for k, v in out.items()}
        parts = med["pre_submit"] + med["queue"] + med["admit_to_first_token"] + med["first_write"]
        _note(result,
              f"ttft legs (p50 ms, {len(out['total'])} requests): pre_submit {med['pre_submit']:.1f} + queue "
              f"{med['queue']:.1f} + admit_to_first_token {med['admit_to_first_token']:.1f} + first_write "
              f"{med['first_write']:.1f} = {parts:.1f}; accepted -> first chunk written p50 {med['total']:.1f}; "
              f"client ttft p50 {client:.1f} of which the generator sent {late:.1f} late")
    result["ttft_legs"] = out
    return out


# ---------------------------------------------------------------------------
# idle time, inside a step and between steps
# ---------------------------------------------------------------------------

def _spans_on_trace_clock(result: Dict[str, Any], offset: float) -> List[Tuple[float, float, str]]:
    """The tracer's spans that can explain an idle gap, on the trace's clock:
    the engine's phases inside each step, and the driver loop's parts."""
    tl = timeline(result)
    spans = []
    for step in tl["steps"]:
        for ph, name, t0, t1, _ in step["events"]:
            if ph == "X":
                spans.append((t0 + offset, t1 + offset, name))
    for step, t0, t1, t2, t3 in tl["drive"]:
        if step is None:
            spans.append((t0 + offset, t1 + offset, "drive.idle"))
        else:
            spans.append((t1 + offset, t2 + offset, "drive.pump"))
            spans.append((t2 + offset, t3 + offset, "drive.yield"))
    return spans


def _innermost_split(gaps: Sequence[Tuple[float, float]], spans: Sequence[Tuple[float, float, str]],
                     fallback: str) -> Dict[str, float]:
    """Seconds of ``gaps`` by the innermost (shortest) span covering each
    instant; ``fallback`` where none does."""
    out: Dict[str, float] = {}
    spans = sorted(spans)
    for a, b in gaps:
        covering = [s for s in spans if s[0] < b and s[1] > a]
        cuts = sorted({a, b, *(min(max(t, a), b) for s in covering for t in s[:2])})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            inner = [s for s in covering if s[0] <= mid < s[1]]
            who = min(inner, key=lambda s: s[1] - s[0])[2] if inner else fallback
            out[who] = out.get(who, 0.0) + (hi - lo)
    return out


def idle_split(result: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """Idle time of the device ``device_idle_share`` reads (the one idle
    longest), split into the part while a ``graft.step`` annotation is open
    and the part while none is; both as a share of the traced window, in
    percent, on the trace's clocks as they are (the device's and the host's
    agree to about a millisecond; the note says how much of the idle time sits
    in gaps shorter than that). ``None`` where the trace holds no such
    annotation. The notes give the split by innermost tracer span."""
    if "idle_split" in result:
        return result["idle_split"]
    out = None
    trace = loaded(result)
    if trace is not None and trace["steps"]:
        lo, hi = trace["window"]
        worst = max(result["reduced"]["devices"], key=lambda d: (hi - lo) - d["busy_s"])
        gaps = xplane.gaps(worst["busy"], lo, hi)
        in_step = xplane.union(xplane.clip(((a, b) for _, a, b in trace["steps"]), lo, hi))
        gaps_in = [g for a, b in gaps for g in xplane.clip(in_step, a, b)]
        gaps_out = xplane.subtract(gaps, in_step)
        window_s = hi - lo
        out = {
            "in_step": 100.0 * xplane.total(gaps_in) / window_s,
            "between": 100.0 * xplane.total(gaps_out) / window_s,
        }
        idle_s = xplane.total(gaps)
        short = sum(b - a for a, b in gaps if b - a < CLOCK_AGREEMENT_S)
        _note(result, f"idle: {idle_s:.3f} s of a {window_s:.3f} s window, {xplane.total(gaps_in):.3f} s "
                      f"while a {STEP_ANNOTATION} is open, {xplane.total(gaps_out):.3f} s between steps; "
                      f"{100.0 * short / max(idle_s, 1e-12):.1f} % of it in gaps shorter than the "
                      f"{CLOCK_AGREEMENT_S * 1e3:.0f} ms the device and host clocks agree to")
        join = clock_join(trace["steps"], (timeline(result) or {}).get("steps", ()))
        if join is not None:
            _note(result, f"clock_join_error_us {join['error_us']:.1f} (p90 of the residuals over "
                          f"{join['steps']} steps, max {join['max_error_us']:.1f}); perf_counter + "
                          f"{join['offset']:.6f} s = the trace's clock")
            spans = _spans_on_trace_clock(result, join["offset"])
            for label, part, fallback in (("idle_in_step", gaps_in, "step (self)"),
                                          ("idle_between_steps", gaps_out, "unattributed")):
                split = sorted(_innermost_split(part, spans, fallback).items(), key=lambda kv: -kv[1])
                _note(result, f"{label} by innermost span: " + ", ".join(
                    f"{k} {v:.3f} s" for k, v in split[:8]))
    result["idle_split"] = out
    return out


def program_run_ms(result: Dict[str, Any], scope: str) -> Optional[List[float]]:
    """Device durations (ms) of the module runs whose program carries the
    program scope ``scope``, by ``program_id``."""
    trace = loaded(result)
    if trace is None:
        return None
    dev = trace["devices"][0]
    roots = program_scope_by_id(dev.ops)
    lo, hi = trace["window"]
    runs = [1e3 * dur for _, pid, start, dur in dev.modules
            if roots.get(pid) == scope and start + dur > lo and start < hi]
    return runs or None
