"""graftscope: the serving engine's flight recorder and span tracer.

Four recorders behind one object (``EngineTracer``, one an engine), all pure
host-side python at the engine's and the front door's existing funnels (the
same choke points the chaos layer hooks), and a fifth that is the process's
(``SETUP``):

- a **ring-buffer step flight recorder** — each ``step()`` owns a list
  of phase events (admit wave, prefill chunk, decode/verify dispatch
  tagged with the ``ProgramRecord`` key, readback, lane_set/table_delta
  flushes) plus instant events (faults, degradation-ladder moves,
  invariant violations); only the last ``PagedConfig.trace_buffer_steps``
  steps are retained, so memory is bounded however long the engine runs;
- a **per-request span recorder** — monotonic ``(timestamp, state)``
  transitions through ``queued → prefilling → active → preempted →
  finished/failed``; terminal requests move to a bounded deque. The
  engine also leaves a ``first_token`` mark (rid, step index) when it
  commits a request's first generated token;
- a **front-door recorder** (``GraftServer`` writes it) — one ``request``
  root per accepted connection, from socket accepted to the last byte of
  the response, with the children ``door.read``, ``door.submit`` (which
  binds the connection to its ``rid``, so the lifecycle states above are
  the same request's children) and ``door.first_write``; and the three
  parts of every turn of the server's driver loop, ``drive.step`` (the
  parent of the engine's step record of the same index), ``drive.pump``
  and ``drive.yield`` (``drive.idle`` while the loop is parked);
- a **routing recorder** — one entry per dispatch of a program with
  experts in a traced engine (``moe/tap.py``): the dispatch paths, the
  pairs computed and the live tokens each expert was routed;
- the **set-up recorder** (``utils/setup_record.py`` ``SETUP``, re-exported
  here) — one a process and always on: named ``setup.*`` spans from the
  process's start to a ready engine, and one event per trace, lowering and
  compile that JAX reports, booked to the span that was open when it
  fired. It lives below this package so that the runtime, the dense engine
  and the trainer's process can write it without loading ``serving``; a
  traced engine's ``timeline()["setup"]`` and ``chrome_events()`` show it.

One clock: while enabled, every step is also a
``jax.profiler.TraceAnnotation("graft.step", step=<index>)``. It costs next
to nothing while no profile is being taken; while one is, it lands on the
host plane of the ``.xplane.pb`` with its ``step`` stat, and a reader joins
it to the step record of the same index — the offset between
``time.perf_counter()`` and the profile's clock puts every span above on
the device trace's timeline (``benchmarks/program_trace.py``).

Everything exports as Chrome trace-event JSON (``chrome://tracing`` /
https://ui.perfetto.dev — pid 0 is the engine step timeline, pid 1 is
one thread per request, pid 2 the server's driver loop, pid 3 the
process's set-up spans) or as jsonl for ad-hoc grepping.

Zero-interference contract (asserted in tests/test_tracing.py and the
graftcheck gate): tracing records around device work, never in it — no
h2d uploads, no extra device syncs, no program-registry changes. When
``enabled`` is False every hook is a single attribute test returning a
shared no-op, so the always-constructed tracer costs nothing. The set-up
recorder has no switch: its listeners run only inside JAX's trace and
compile path.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

# the process's set-up recorder lives below every layer that opens a span of
# it; this module draws it (``timeline()``, ``chrome_events()``)
from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP

# request states that end a span and retire it to the done-deque
TERMINAL_STATES = ("finished", "failed")

# The vocabulary of ``jax.named_scope`` names on the device work. A scope is
# a segment of an HLO instruction's ``op_name`` path (``tf_op`` in a device
# trace), so the readers in ``benchmarks/`` match whole segments of it.
# PROGRAM_SCOPES open a jitted program (``_register_program`` enters the
# program's kind, ``make_train_step`` enters ``train_step``; the jitted
# callable keeps its name); BLOCK_SCOPES sit where the blocks are defined, so
# training and serving share them; CHILD_SCOPES only ever appear under their
# parent (``attn/qkv``, ``moe/router``).
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
    f"{parent}/{child}"
    for parent, children in CHILD_SCOPES.items() for child in children
)

# Finer scopes below the vocabulary, for one cell's own readers: the shared
# readers (``benchmarks/program_trace.py`` keeps a copy of SCOPES) do not know
# them and book their ops to the parent. ``attn/qk_norm`` is OLMoE's joint
# RMSNorm of q and k (models/llama.py); ``moe/experts/selective`` and
# ``moe/experts/all`` say which no-drop dispatch path a program took
# (moe/experts.py); ``attn/latent_down`` (``W_DKV`` and the latent's norm),
# ``attn/latent_up`` (``W_UKV`` over the rows attended) and ``attn/absorb``
# (``W_UK`` / ``W_UV`` folded into the query and the output) are latent
# attention's (models/sarvam.py); ``moe/shared`` the shared expert
# (moe/model.py); ``attn/gate`` (the per-kv-head decay), ``attn/retention/expand``
# (φ of q and k), ``attn/retention/chunk`` (prefill: the in-chunk weights, the
# carried state's read and its update) and ``attn/retention/step`` (decode: a
# state's read and update) are power retention's (models/brumby.py);
# ``attn/full`` and ``attn/window`` wrap the attention block of a layer of
# that kind — the block's usual children sit below them
# (``attn/window/kv_read``) and the shared readers book them to ``attn/kv_read``
# as ever — and ``attn/out_gate`` is the per-head output gate (models/laguna.py);
# a layer's ``moe/router`` may precede its ``attn`` (models/smallthinker.py
# routes from the layer's normed input, before attention) with ``moe/experts``
# after it, so a layer opens ``moe`` twice: the shared readers book both to ``moe``;
# ``attn/q_latent`` is the query's down-projection and its norm, and ``mhc`` —
# outside every block: the readers' copy of BLOCK_SCOPES is the benchmark's to
# change, so they book it to the program's root — is the multi-stream residual
# around a sub-layer: ``mhc/coeff`` (the streams' norm and the three
# coefficient products), ``mhc/sinkhorn`` (the rounds that project the residual
# mix) and ``mhc/mix`` (the pre-collapse, the post-spread and the residual mix)
# (models/xing.py); ``attn/ssm`` wraps a Mamba mixer — its two projections sit
# under it bare — with ``attn/ssm/conv`` (the causal convolution and its tail),
# ``attn/ssm/params`` (``x_proj``, the three inner norms, ``dt_proj``, the
# softplus), ``attn/ssm/scan`` (prefill: a block of rows one after another from
# the carried state, the state's way out of its slot and back) and
# ``attn/ssm/step`` (decode: one pass over the lanes' slots) (models/jamba.py);
# ``attn/sparse`` is a block-sparse attention layer's own work — ``pool_keys``
# (the kernels the fresh rows complete: their rows read back, the mean, the
# write), ``select`` (the lane's pooled keys read and scored, the blocks
# chosen) and ``read`` (the chosen blocks gathered and attended, or a chunk's
# walk over the context's tiles) — and ``attn/lightning`` a Lightning layer's:
# ``chunk`` (prefill: the chunk form, the state's way out of its slot and
# back), ``step`` (decode: the lanes' states out, one update each, back) and
# ``gate_norm`` (the output norm) (models/minicpm_sala.py).
DETAIL_SCOPES = {
    "": ("mhc",),
    "attn": ("qk_norm", "latent_down", "latent_up", "absorb", "gate", "retention",
             "full", "window", "out_gate", "q_latent", "ssm", "sparse", "lightning"),
    "attn/retention": ("expand", "chunk", "step"),
    "attn/ssm": ("conv", "params", "scan", "step"),
    "attn/sparse": ("pool_keys", "select", "read"),
    "attn/lightning": ("chunk", "step", "gate_norm"),
    "mhc": ("coeff", "sinkhorn", "mix"),
    "moe": ("shared",),
    "moe/experts": ("selective", "all"),
}

# the annotation every traced step opens on the profiler's host timeline
STEP_ANNOTATION = "graft.step"

# event tuple layout inside a step record: (ph, name, t0, t1, args)
# ph "X" = duration slice (t1 = end), ph "i" = instant (t1 unused)


def program_label(record: Any) -> str:
    """Human-readable dispatch tag for a ``ProgramRecord`` (PR 9's
    registry): kind plus the sorted meta dict, e.g.
    ``pdecode[gather=False,kv_limit=32]``. Takes any object with
    ``kind``/``meta`` attributes so tracing never imports the analysis
    layer."""
    kind = getattr(record, "kind", None) or record.__class__.__name__
    meta = getattr(record, "meta", None) or {}
    inner = ",".join(f"{k}={v}" for k, v in sorted(meta.items()))
    return f"{kind}[{inner}]" if inner else str(kind)


class _NullSpan:
    """Shared do-nothing context manager returned by ``phase`` when
    tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0")

    def __init__(self, tracer: "EngineTracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._tracer.complete(self._name, self._t0, time.perf_counter(),
                              **self._args)
        return False


class EngineTracer:
    """Flight recorder + request-span tracer (see module docstring)."""

    def __init__(self, enabled: bool = False, buffer_steps: int = 256,
                 max_requests: int = 4096):
        self.enabled = bool(enabled)
        self.buffer_steps = max(int(buffer_steps), 1)
        self._steps: deque = deque(maxlen=self.buffer_steps)
        self._cur: Optional[List[tuple]] = None
        self._step_idx = 0
        self._step_t0 = 0.0
        # rid -> [(ts, state), ...] for live requests; terminal spans
        # retire to _done so memory stays bounded under churn
        self._spans: Dict[int, List[Tuple[float, str]]] = {}
        self._done: deque = deque(maxlen=max(int(max_requests), 1))
        # front door: one record per accepted connection (open ones by rid
        # once bound), marks (name, ts, rid, args), driver-loop turns
        self._conn = 0
        self._doors: Dict[int, dict] = {}
        self._doors_done: deque = deque(maxlen=max(int(max_requests), 1))
        self._marks: deque = deque(maxlen=max(int(max_requests), 1))
        self._drive: deque = deque(maxlen=self.buffer_steps)
        # what construction did, written once by a traced engine's prewarm
        # (``_setup_facts``): relaid_leaves, relaid_bytes,
        # program_temp_bytes_max, cache_row_bytes and — where the cache, or a
        # kind of it, is a state a lane — state_bytes_per_lane; cache_kinds
        # (a kind: layers, rows_per_lane, row_bytes or state_bytes,
        # decode_read); residual_row_bytes where the residual has several
        # streams. A decode dispatch record carries ``rows`` and, where some
        # kind is a state, ``state_lanes`` (live lanes) and
        # ``state_slots_passed`` (slots the pass moved); where a kind's
        # layers choose the blocks they read, ``sparse_rows_cached``,
        # ``sparse_rows_read`` and ``sparse_blocks_forced`` (a layer's, summed
        # over the live lanes)
        self.setup: Dict[str, int] = {}
        # routing counters, one entry per dispatch of a tapped program
        # (moe/tap.py): (step, kind, dispatch paths, pairs computed, live
        # tokens per expert). The counts stay the device arrays the program
        # returned until ``timeline()`` is asked for them.
        self._routed: deque = deque(maxlen=4 * self.buffer_steps)
        # the open step's jax.profiler.TraceAnnotation, if any
        self._annotation: Any = None
        self._annotate = None
        if self.enabled:
            from jax.profiler import TraceAnnotation

            self._annotate = TraceAnnotation

    # ------------------------------------------------------------------
    # recording hooks (every one is a no-op unless enabled)
    # ------------------------------------------------------------------

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def begin_step(self, index: int) -> None:
        if not self.enabled:
            return
        if self._annotation is not None:   # a step that raised never ended
            self._annotation.__exit__(None, None, None)
        self._cur = []
        self._step_idx = index
        # t0 and the annotation's start are the two ends of the clock join:
        # nothing runs between them
        self._annotation = self._annotate(STEP_ANNOTATION, step=index)
        self._step_t0 = time.perf_counter()
        self._annotation.__enter__()

    def end_step(self, **args: Any) -> None:
        if not self.enabled or self._cur is None:
            return
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        self._steps.append({
            "step": self._step_idx,
            "t0": self._step_t0,
            "t1": time.perf_counter(),
            "events": self._cur,
            "args": args,
        })
        self._cur = None

    def phase(self, name: str, **args: Any):
        """Context manager recording a duration slice for an engine phase
        inside the current step. Use :meth:`complete` instead at sites
        that already keep their own perf_counter pair."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def complete(self, name: str, t0: float, t1: Optional[float] = None,
                 **args: Any) -> None:
        if not self.enabled or self._cur is None:
            return
        self._cur.append(
            ("X", name, t0, time.perf_counter() if t1 is None else t1, args))

    def instant(self, name: str, **args: Any) -> None:
        """Point event (fault fired, ladder moved, invariant violated).
        Instants between steps (no step open) are dropped — every engine
        site that emits one runs inside ``step()``."""
        if not self.enabled or self._cur is None:
            return
        self._cur.append(("i", name, time.perf_counter(), None, args))

    def request_state(self, rid: int, state: str) -> None:
        if not self.enabled:
            return
        self._spans.setdefault(rid, []).append((time.perf_counter(), state))
        if state in TERMINAL_STATES:
            self._done.append((rid, self._spans.pop(rid)))

    def routed(self, step: int, kind: str, paths: Tuple[str, ...],
               pairs_computed: int, tokens_per_expert: Any,
               held: Optional[Tuple[int, int]] = None) -> None:
        """One dispatch of a tapped program in engine step ``step``; ``held``
        = (first id, count) of the experts the model holds (all, if None)."""
        self._routed.append((step, kind, paths, pairs_computed, tokens_per_expert, held))

    def mark(self, name: str, rid: int, **args: Any) -> None:
        """Point event of one request (``first_token``)."""
        if not self.enabled:
            return
        self._marks.append((name, time.perf_counter(), rid, args))

    # -- front door (GraftServer) ------------------------------------------

    def open_request(self) -> Optional[dict]:
        """Root ``request`` record of a connection just accepted, or None
        when tracing is off — the server keeps the record while it handles
        the connection and tests it, not ``enabled``, at every later hook."""
        if not self.enabled:
            return None
        self._conn += 1
        return {"conn": self._conn, "rid": None, "t0": time.perf_counter(),
                "t1": None, "spans": [], "first_pump": None}

    @staticmethod
    def door_span(door: dict, name: str, t0: float, t1: float) -> None:
        """Child span of a ``request`` root."""
        door["spans"].append((name, t0, t1))

    def bind_request(self, door: dict, rid: int) -> None:
        """``engine.submit`` returned: the connection is request ``rid``."""
        door["rid"] = rid
        self._doors[rid] = door

    def note_first_pump(self, rid: int) -> None:
        """The server queued ``rid``'s first token for its stream: where
        ``door.first_write`` starts."""
        door = self._doors.get(rid)
        if door is not None and door["first_pump"] is None:
            door["first_pump"] = time.perf_counter()

    def close_request(self, door: dict) -> None:
        """Last byte of the response written."""
        door["t1"] = time.perf_counter()
        if door["rid"] is not None:
            self._doors.pop(door["rid"], None)
        self._doors_done.append(door)

    def drive_turn(self, step: int, t0: float, t1: float, t2: float,
                   t3: float) -> None:
        """One turn of the server's driver loop: ``drive.step`` [t0, t1)
        around ``engine.step()`` number ``step``, ``drive.pump`` [t1, t2),
        ``drive.yield`` [t2, t3) in which the loop ran its other tasks."""
        self._drive.append((step, t0, t1, t2, t3))

    def drive_idle(self, t0: float, t1: float) -> None:
        """The driver parked with no work: ``drive.idle``."""
        self._drive.append((None, t0, t1, t1, t1))

    def timeline(self) -> dict:
        """Everything recorded, as plain lists on ``time.perf_counter()``'s
        clock — what the benchmark's readers take: ``steps`` (the flight
        recorder's records), ``requests`` (front-door roots, finished and
        open), ``states`` (rid -> [(ts, state)]), ``marks`` [(name, ts,
        rid, args)], ``drive`` [(step, t0, t1, t2, t3)], ``setup`` (a traced
        engine's construction counts and, beside them, the process's set-up
        record: ``origin``, ``spans``, ``events`` — ``utils/setup_record.py``),
        ``routed`` [(step, program kind,
        dispatch paths, (token, expert) pairs computed, [live tokens — no
        bucket padding, no idle lane — routed to each expert, summed over
        layers])] per dispatch of a program with experts, ``routed_local``
        [of those live pairs, the ones routed to an expert the model holds —
        all of them unless it holds a share of the router's] beside it
        (reading them waits for the device)."""
        states = {rid: list(trans) for rid, trans in self._done}
        states.update({rid: list(t) for rid, t in self._spans.items()})
        routed = list(self._routed)
        counts = []
        if routed:
            import jax

            counts = jax.device_get([row[4] for row in routed])    # one batched fetch
        return {
            "steps": list(self._steps),
            "requests": list(self._doors_done) + list(self._doors.values()),
            "states": states,
            "marks": list(self._marks),
            "drive": list(self._drive),
            "setup": {**self.setup, **SETUP.record()} if self.enabled else {},
            "routed": [
                (step, kind, paths, pairs, [int(n) for n in per_expert])
                for (step, kind, paths, pairs, *_), per_expert in zip(routed, counts)
            ],
            "routed_local": [
                int(sum(per_expert[slice(held[0], held[0] + held[1]) if held else slice(None)]))
                for (*_, held), per_expert in zip(routed, counts)
            ],
        }

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------

    @staticmethod
    def _us(t: float) -> float:
        return round(t * 1e6, 1)

    def chrome_events(self) -> List[dict]:
        """Flatten the recorders into Chrome trace-event dicts: pid 0 =
        engine step timeline (one outer slice per step, phase slices and
        instants nested inside), pid 1 = requests (tid = rid, one slice
        per lifecycle state, instants at terminal transitions, the front
        door's ``request`` root and ``door.*`` children and the
        ``first_token`` mark on the same thread; a connection that never
        became a request sits on thread ``-conn``), pid 2 = the server's
        driver loop (``drive.step`` / ``drive.pump`` / ``drive.yield``),
        pid 3 = the process's set-up spans (``SETUP``), so an exported trace
        shows a start beside the steps it led to."""
        evs: List[dict] = [
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": "engine steps"}},
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "requests"}},
            {"ph": "M", "name": "process_name", "pid": 2, "tid": 0,
             "args": {"name": "server driver loop"}},
            {"ph": "M", "name": "process_name", "pid": 3, "tid": 0,
             "args": {"name": "setup"}},
        ]
        for name, t0, t1, _parent, args in list(SETUP.spans) if self.enabled else ():
            if t1 is not None:
                evs.append({"ph": "X", "name": name, "cat": "setup", "pid": 3,
                            "tid": 0, "ts": self._us(t0),
                            "dur": self._us(t1 - t0), "args": args})
        for rec in self._steps:
            evs.append({
                "ph": "X", "name": f"step {rec['step']}", "cat": "step",
                "pid": 0, "tid": 0, "ts": self._us(rec["t0"]),
                "dur": self._us(rec["t1"] - rec["t0"]),
                "args": {"step": rec["step"], **rec["args"]},
            })
            for ph, name, t0, t1, args in rec["events"]:
                ev = {"ph": ph, "name": name, "cat": "phase", "pid": 0,
                      "tid": 0, "ts": self._us(t0), "args": args}
                if ph == "X":
                    ev["dur"] = self._us(t1 - t0)
                else:
                    ev["cat"] = "event"
                    ev["s"] = "p"       # process-scoped instant
                evs.append(ev)
        live = [(rid, list(trans)) for rid, trans in self._spans.items()]
        for rid, trans in list(self._done) + live:
            evs.append({"ph": "M", "name": "thread_name", "pid": 1,
                        "tid": rid, "args": {"name": f"request {rid}"}})
            for i, (ts, state) in enumerate(trans):
                if state in TERMINAL_STATES:
                    evs.append({"ph": "i", "name": state, "cat": "request",
                                "pid": 1, "tid": rid, "ts": self._us(ts),
                                "s": "t", "args": {"rid": rid}})
                    continue
                # a state lasts until the next transition; a live request's
                # current state renders as a zero-width slice at its edge
                end = trans[i + 1][0] if i + 1 < len(trans) else ts
                evs.append({"ph": "X", "name": state, "cat": "request",
                            "pid": 1, "tid": rid, "ts": self._us(ts),
                            "dur": self._us(end - ts),
                            "args": {"rid": rid, "parent": "request"}})
        for door in list(self._doors_done) + list(self._doors.values()):
            rid, conn = door["rid"], door["conn"]
            tid = rid if rid is not None else -conn
            ids = {"rid": rid, "conn": conn}
            end = door["t1"] if door["t1"] is not None else door["t0"]
            evs.append({"ph": "X", "name": "request", "cat": "door",
                        "pid": 1, "tid": tid, "ts": self._us(door["t0"]),
                        "dur": self._us(end - door["t0"]), "args": ids})
            for name, t0, t1 in door["spans"]:
                evs.append({"ph": "X", "name": name, "cat": "door",
                            "pid": 1, "tid": tid, "ts": self._us(t0),
                            "dur": self._us(t1 - t0),
                            "args": {**ids, "parent": "request"}})
        for name, ts, rid, args in self._marks:
            evs.append({"ph": "i", "name": name, "cat": "request", "pid": 1,
                        "tid": rid, "ts": self._us(ts), "s": "t",
                        "args": {"rid": rid, **args}})
        for step, t0, t1, t2, t3 in self._drive:
            if step is None:
                parts = (("drive.idle", t0, t1),)
            else:
                parts = (("drive.step", t0, t1), ("drive.pump", t1, t2),
                         ("drive.yield", t2, t3))
            for name, a, b in parts:
                evs.append({"ph": "X", "name": name, "cat": "drive",
                            "pid": 2, "tid": 0, "ts": self._us(a),
                            "dur": self._us(b - a), "args": {"step": step}})
        return evs

    def export(self, path: str, fmt: str = "chrome") -> str:
        """Write the trace to ``path``; ``fmt`` is ``chrome`` (trace-event
        JSON, perfetto-viewable) or ``jsonl`` (one event per line).
        Returns ``path``."""
        events = self.chrome_events()
        if fmt == "chrome":
            with open(path, "w") as f:
                json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                          f, default=str)
        elif fmt == "jsonl":
            with open(path, "w") as f:
                for ev in events:
                    f.write(json.dumps(ev, default=str) + "\n")
        else:
            raise ValueError(f"unknown trace format {fmt!r}")
        return path
