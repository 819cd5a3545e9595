"""graftplan: offline schedule synthesis over the policy seam.

The fourth analyzer (after shardlint, graftcheck, graftsched): close the
loop between graftsched's legality automaton and graftmeter's analytic
cost model by *searching* the step-policy space offline, on a recorded
workload, with no device and no jit — then shipping the winner as a
machine-checked **policy table** artifact the serving engine loads under
rule **GC011**. Three pieces:

1. **Trace-replay simulator** (:class:`Simulator`, :func:`simulate`): a
   deterministic step-level replay of a recorded workload
   (:meth:`PagedServingEngine.export_workload` — request arrivals +
   classes + the engine's pool/ladder geometry, distilled from the
   ``action_trace`` steps and graftscope request-lifecycle spans). The
   simulator mirrors the engine's scheduling semantics transition-for-
   transition — admission waves with head-of-line block accounting,
   chunked prefill with aggregate budgets, sync decode with preempt-on-
   pool-dry, the depth-1 async lookahead with lame-duck drains — and
   every action it emits is validated against the graftsched
   :data:`~.graftsched.AUTOMATON` via :func:`~.graftsched.advance`, so a
   simulator bug that would emit an illegal schedule is a finding, not a
   silently wrong cost estimate. Per-action costs come from graftmeter's
   :func:`~..serving.accounting.analytic_cost` at the dispatched bucket
   rung (pad-waste priced in by construction: cost is bucket-shaped, not
   need-shaped).

2. **Policy autotuner** (:class:`PolicyVector`, :func:`synthesize`):
   seeded random sampling + coordinate descent over a typed vector —
   per-class admission weights, class burn boost, prefill chunk budget
   per burn state (quantized to the prefill ladder), verify cadence,
   sync/async preference — scored by the simulator's analytic objective:
   simulated makespan inflated by the per-class SLO burn the
   :mod:`~..serving.slo` machinery defines (fraction of observations
   over target / error budget).

3. **Certified policy tables** (:func:`build_table`,
   :func:`check_policy_table`, :func:`load_policy_table`): the emitted
   JSON artifact carries fingerprints of the automaton edge table, the
   catalog bucket ladders, and the source workload trace, plus a
   certificate stamped by replaying the candidate
   :class:`~..serving.scheduler.TablePolicy` live through the graftsched
   explorer harness (per-action invariant audits + leak check, GC010).
   Rule **GC011** re-checks all of it at load time: a table with a
   missing/unclean certificate, a stale automaton or ladder fingerprint,
   or an out-of-ladder chunk budget is rejected with a finding naming
   the stale component.

Like graftsched, this module never imports jax — synthesis runs on a
workload dict (CI, a laptop) without touching a device. Only the
certification step needs a live CPU engine, and only the gate script
(`scripts/graftplan_gate.py`) drives that.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from neuronx_distributed_llama3_2_tpu import flops as flops_mod
from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
    AUTOMATON,
    Finding,
    ScheduleState,
    advance,
)
from neuronx_distributed_llama3_2_tpu.serving.accounting import (
    EngineDims,
    analytic_cost,
)
from neuronx_distributed_llama3_2_tpu.serving.catalog import pick_bucket
from neuronx_distributed_llama3_2_tpu.serving.policy import (
    ActionType,
    QueuedRequest,
    StepAction,
)
from neuronx_distributed_llama3_2_tpu.serving.slo import SLOPolicy

__all__ = [
    "GC011",
    "PolicyTableError",
    "PolicyVector",
    "SimResult",
    "Simulator",
    "SynthesisResult",
    "Workload",
    "WorkloadRequest",
    "automaton_fingerprint",
    "build_table",
    "certify_table",
    "check_policy_table",
    "fifo_vector",
    "ladder_fingerprint",
    "load_policy_table",
    "simulate",
    "synthesize",
    "trace_fingerprint",
]

#: The load-time policy-table rule this module owns (registered in the
#: graftcheck GC catalogue; see analysis/graftcheck.py GC_RULES).
GC011 = "GC011"

#: Burn states a prefill chunk budget is keyed by: the same three-way
#: branch SloPolicy's budget logic takes on the global burn gauges.
BURN_STATES = ("calm", "ttft_burn", "tpot_burn")

#: Host scheduling cost charged per executed action (ms) — the analytic
#: stand-in for the engine's measured ``host_schedule_ms`` share.
HOST_OVERHEAD_MS = 0.02

#: Fixed per-dispatch launch overhead (ms) added on top of the roofline
#: time of every device program the simulator prices.
DISPATCH_OVERHEAD_MS = 0.05

#: Objective weight on the summed per-class burns: the makespan is
#: inflated by ``1 + weight * sum(min(burn, cap))`` so an SLO-burning
#: schedule loses to a slightly slower one that meets its objectives.
BURN_OBJECTIVE_WEIGHT = 0.5
BURN_CAP = 100.0  # one full window over target at a p99 budget


# -- fingerprints -----------------------------------------------------------


def _sha(obj: Any) -> str:
    return hashlib.sha1(
        json.dumps(obj, sort_keys=True, default=str).encode()
    ).hexdigest()


def automaton_fingerprint() -> str:
    """Digest of the graftsched AUTOMATON edge table. A policy table is
    only valid against the exact legality rules it was certified under —
    editing an automaton edge stales every outstanding table."""
    return _sha([dict(e) for e in AUTOMATON])


def ladder_fingerprint(
    prefill_buckets: Sequence[int], kv_buckets: Sequence[int]
) -> str:
    """Digest of the catalog bucket ladders the table's budgets and the
    simulator's bucket-shaped costs were computed against."""
    return _sha({
        "prefill": [int(b) for b in prefill_buckets],
        "kv": [int(b) for b in kv_buckets],
    })


def trace_fingerprint(workload_dict: Mapping[str, Any]) -> str:
    """Digest of the source workload trace (geometry + request spans)."""
    return _sha({
        "config": workload_dict.get("config"),
        "requests": workload_dict.get("requests"),
    })


# -- workload model ---------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkloadRequest:
    """One recorded request span: everything the simulator needs to
    replay its lifecycle (token *values* never matter — only counts)."""

    rid: int
    prompt_tokens: int
    max_new_tokens: int
    service_class: str = "batch"
    tenant: str = "default"
    #: engine ``_step_index`` at submit() time — requests recorded
    #: mid-run arrive in the simulator at the same step boundary
    submitted_step: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Workload:
    """A recorded workload trace: the engine geometry + request spans
    :meth:`PagedServingEngine.export_workload` serializes, as plain data
    (no engine, no jax) the simulator and autotuner run on."""

    block_size: int
    num_blocks: int
    decode_reserve_blocks: int
    lanes: int
    max_seq_len: int
    prefill_chunk_tokens: Optional[int]
    prefill_buckets: Tuple[int, ...]
    kv_buckets: Tuple[int, ...]
    dims: EngineDims
    requests: List[WorkloadRequest]
    slo_ttft_p99_ms: Optional[float] = None
    slo_tpot_p99_ms: Optional[float] = None
    #: summary of the recorded action trace (graftscope/graftsched side
    #: of the export) — fingerprinted into the artifact, not replayed
    trace: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "version": 1,
            "config": {
                "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "decode_reserve_blocks": self.decode_reserve_blocks,
                "lanes": self.lanes,
                "max_seq_len": self.max_seq_len,
                "prefill_chunk_tokens": self.prefill_chunk_tokens,
                "prefill_buckets": list(self.prefill_buckets),
                "kv_buckets": list(self.kv_buckets),
                "slo_ttft_p99_ms": self.slo_ttft_p99_ms,
                "slo_tpot_p99_ms": self.slo_tpot_p99_ms,
                "dims": dataclasses.asdict(self.dims),
            },
            "requests": [r.to_dict() for r in self.requests],
            "trace": dict(self.trace),
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Workload":
        """An artifact written while the look-ahead was an option carries
        that option's flag in its ``config`` block: the key is read past —
        the request spans and the geometry are what a replay needs, and
        every engine now steps the way the flag's ``true`` did."""
        cfg = d["config"]
        return cls(
            block_size=int(cfg["block_size"]),
            num_blocks=int(cfg["num_blocks"]),
            decode_reserve_blocks=int(cfg["decode_reserve_blocks"]),
            lanes=int(cfg["lanes"]),
            max_seq_len=int(cfg["max_seq_len"]),
            prefill_chunk_tokens=cfg.get("prefill_chunk_tokens"),
            prefill_buckets=tuple(cfg["prefill_buckets"]),
            kv_buckets=tuple(cfg["kv_buckets"]),
            slo_ttft_p99_ms=cfg.get("slo_ttft_p99_ms"),
            slo_tpot_p99_ms=cfg.get("slo_tpot_p99_ms"),
            dims=EngineDims(**cfg["dims"]),
            requests=[WorkloadRequest(**r) for r in d["requests"]],
            trace=dict(d.get("trace", {})),
        )

    @property
    def slo(self) -> SLOPolicy:
        return SLOPolicy(
            ttft_p99_ms=self.slo_ttft_p99_ms,
            tpot_p99_ms=self.slo_tpot_p99_ms,
        )

    def classes(self) -> List[str]:
        return sorted({r.service_class for r in self.requests})


# -- policy vector ----------------------------------------------------------


@dataclasses.dataclass
class PolicyVector:
    """The typed point the autotuner searches: every schedulable degree
    of freedom the policy seam exposes, and nothing the automaton could
    reject (TablePolicy keeps the FIFO arm *structure*; a vector only
    bends ADMIT ordering, PREFILL_CHUNK budgets, and the spec/async
    choice points)."""

    #: service class -> admission weight (lower admits earlier). Classes
    #: absent here rank behind every listed one.
    class_weight: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"interactive": 0.0, "batch": 1.0}
    )
    #: weight subtracted from a class burning its SLO budget — the
    #: table twin of scheduler.BURN_BOOST
    burn_boost: float = 2.0
    #: burn state -> aggregate prefill-chunk token budget per step; each
    #: value must be a prefill-ladder rung (GC011 rejects otherwise).
    #: Empty = unbudgeted (FIFO's historical unbounded wave).
    prefill_budget: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: attempt a VERIFY (speculative) arm every N steps (spec engines
    #: only; 1 = every step, the FIFO default)
    verify_cadence: int = 1
    #: take the async lookahead arm when eligible
    prefer_async: bool = True

    def to_dict(self) -> dict:
        return {
            "class_weight": dict(self.class_weight),
            "burn_boost": self.burn_boost,
            "prefill_budget": dict(self.prefill_budget),
            "verify_cadence": self.verify_cadence,
            "prefer_async": self.prefer_async,
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "PolicyVector":
        return cls(
            class_weight={
                str(k): float(v)
                for k, v in dict(d.get("class_weight", {})).items()
            },
            burn_boost=float(d.get("burn_boost", 0.0)),
            prefill_budget={
                str(k): int(v)
                for k, v in dict(d.get("prefill_budget", {})).items()
            },
            verify_cadence=max(int(d.get("verify_cadence", 1)), 1),
            prefer_async=bool(d.get("prefer_async", True)),
        )

    def rank(self, service_class: str, burning: bool) -> float:
        known = self.class_weight.values()
        default = (max(known) + 1.0) if self.class_weight else 0.0
        w = self.class_weight.get(service_class, default)
        return w - (self.burn_boost if burning else 0.0)

    def budget_for(self, state: str) -> Optional[int]:
        b = self.prefill_budget.get(state)
        return int(b) if b else None


def fifo_vector() -> PolicyVector:
    """The identity point: FCFS admission (equal weights, no boost), no
    prefill budget, verify every step, async preferred — simulates
    action-for-action as FifoPolicy schedules."""
    return PolicyVector(
        class_weight={}, burn_boost=0.0, prefill_budget={},
        verify_cadence=1, prefer_async=True,
    )


# -- the simulator ----------------------------------------------------------


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass
class _SimReq:
    spec: WorkloadRequest
    out: int = 0                   # generated tokens so far
    lane: Optional[int] = None
    blocks: int = 0                # blocks held (len(req.table) live)
    position: int = 0
    prefilling: bool = False
    prefill_pos: int = 0
    prefill_target: int = 0
    preemptions: int = 0
    done: bool = False
    submitted_ms: float = 0.0
    first_token_ms: Optional[float] = None
    finished_ms: Optional[float] = None

    @property
    def rid(self) -> int:
        return self.spec.rid

    @property
    def seq_len(self) -> int:
        return self.spec.prompt_tokens + self.out


@dataclasses.dataclass
class SimResult:
    """Everything one simulator run measures. ``findings`` holds any
    automaton rejections of the simulator's own schedule (always empty
    unless the simulator itself is broken — asserted by the gate)."""

    steps: int
    dispatches: int
    actions: int
    makespan_ms: float
    device_ms: float
    host_ms: float
    prefill_pad_tokens: int
    decode_pad_tokens: int
    admission_order: List[int]
    per_class_tokens: Dict[str, int]
    ttft_ms: Dict[int, float]
    tpot_ms: Dict[int, float]
    burn_by_class: Dict[str, Dict[str, float]]
    objective: float
    preemptions: int
    finished: List[int]
    findings: List[Finding]


class Simulator:
    """Deterministic step-level replay of a :class:`Workload` under a
    :class:`PolicyVector` (None = FIFO). Mirrors the engine's scheduling
    semantics exactly — the simulator-vs-live calibration test pins step
    counts, admission order, and per-class token totals — while pricing
    every dispatch with graftmeter's analytic roofline at the padded
    bucket rung. No device, no jit, no jax."""

    def __init__(
        self, workload: Workload, vector: Optional[PolicyVector] = None
    ) -> None:
        self.w = workload
        self.vec = vector or fifo_vector()
        self._fifo = vector is None
        self.dims = workload.dims
        self.findings: List[Finding] = []
        self._state = ScheduleState()
        self._step = 0
        self._clock_ms = 0.0
        self._device_ms = 0.0
        self._host_ms = 0.0
        self._step_host_ms = 0.0
        self._step_device_ms = 0.0
        self._step_async = False
        self._dispatches = 0
        self._actions = 0
        self._prefill_pad = 0
        self._decode_pad = 0
        self._admission_order: List[int] = []
        # engine twin state
        self._reqs = [
            _SimReq(spec=r)
            for r in sorted(workload.requests, key=lambda r: r.rid)
        ]
        self._arrivals = sorted(
            self._reqs, key=lambda r: (r.spec.submitted_step, r.rid)
        )
        self._arrived = 0
        self._queue: List[_SimReq] = []
        self._active: Dict[int, _SimReq] = {}
        self._free_lanes = list(range(workload.lanes))
        self._usable_blocks = max(workload.num_blocks - 1, 0)
        self._free_blocks = self._usable_blocks
        self._pending: Optional[List[int]] = None  # async in-flight lanes
        self._frontier: Dict[int, int] = {}  # positions mirror per lane
        self._finished: List[int] = []
        self._preemptions = 0
        self._dirty_lanes: set = set()
        self._table_deltas = 0

    # -- bookkeeping --------------------------------------------------------

    def _emit(self, atype: ActionType, mode: str = "", **meta) -> None:
        act = StepAction(atype, mode=mode, meta=meta)
        self._actions += 1
        self._host_ms += HOST_OVERHEAD_MS
        self._step_host_ms += HOST_OVERHEAD_MS
        self.findings.extend(
            advance(self._state, act, f"sim step {self._step}")
        )

    def _charge(self, key: tuple, pad: int, kind: str) -> None:
        f, byts, _src = analytic_cost(key, self.dims)
        peaks = flops_mod.chip_peaks()
        t = max(f / peaks.bf16_flops, byts / peaks.hbm_bw) * 1e3
        t += DISPATCH_OVERHEAD_MS
        self._device_ms += t
        self._step_device_ms += t
        self._dispatches += 1
        if kind == "prefill":
            self._prefill_pad += pad
        else:
            self._decode_pad += pad

    def _kv_bucket(self, needed: int) -> int:
        for b in self.w.kv_buckets:
            if b >= needed:
                return int(b)
        return int(self.w.kv_buckets[-1])

    def _flush(self) -> None:
        if self._table_deltas:
            self._emit(
                ActionType.TABLE_DELTA_FLUSH, n=self._table_deltas,
                in_flight=self._pending is not None,
            )
            self._table_deltas = 0
        if self._dirty_lanes:
            self._emit(
                ActionType.LANE_SET_FLUSH,
                lanes=sorted(self._dirty_lanes),
                in_flight=self._pending is not None,
            )
            self._dirty_lanes.clear()

    # -- request lifecycle --------------------------------------------------

    def _now(self) -> float:
        """Provisional clock inside a step: the committed clock plus the
        costs charged so far this step (timestamps land mid-step, like
        the live engine's perf_counter stamps)."""
        return self._clock_ms + self._step_device_ms + self._step_host_ms

    def _commit_token(self, req: _SimReq, cap_check: bool = False) -> None:
        req.out += 1
        if req.first_token_ms is None:
            req.first_token_ms = self._now()
        if cap_check and req.position >= self.w.max_seq_len - 1:
            # readback-path sequence cap (live _read_and_apply); prefill
            # commits never set done-by-position
            req.done = True

    def _finish_due(self, req: _SimReq) -> bool:
        return req.done or req.out >= req.spec.max_new_tokens

    def _maybe_finish(self, req: _SimReq) -> None:
        if not self._finish_due(req) or req.rid in self._finished:
            return
        req.done = True
        lane = req.lane
        if lane is not None:
            self._release_lane(req)
        self._emit(ActionType.FINISH, rid=req.rid, lane=lane, failed=False)
        req.finished_ms = self._now()
        self._finished.append(req.rid)

    def _release_lane(self, req: _SimReq) -> None:
        lane = req.lane
        self._free_blocks += req.blocks
        req.blocks = 0
        del self._active[lane]
        self._free_lanes.append(lane)
        self._frontier[lane] = 0
        self._dirty_lanes.add(lane)
        req.lane = None

    def _preempt(self, req: _SimReq) -> None:
        lane = req.lane
        self._release_lane(req)
        req.position = 0
        req.prefilling = False
        req.prefill_pos = 0
        req.prefill_target = 0
        self._queue.insert(0, req)
        req.preemptions += 1
        self._preemptions += 1
        self._emit(ActionType.PREEMPT, rid=req.rid, lane=lane, shed=False)

    # -- burn gauges (offline projection of the SLOMonitor) -----------------

    def _burns(self) -> Tuple[Dict[str, Dict[str, float]], float, float]:
        slo = self.w.slo
        per_class: Dict[str, Dict[str, float]] = {}
        totals = {"ttft": [0, 0], "tpot": [0, 0]}
        for req in self._reqs:
            row: List[Tuple[str, Optional[float], Optional[float]]] = []
            if req.first_token_ms is not None:
                row.append((
                    "ttft", slo.ttft_p99_ms,
                    req.first_token_ms - req.submitted_ms,
                ))
            if req.finished_ms is not None and req.out > 1:
                row.append((
                    "tpot", slo.tpot_p99_ms,
                    (req.finished_ms - req.first_token_ms) / (req.out - 1),
                ))
            for kind, target, value in row:
                if target is None or value is None:
                    continue
                cls = per_class.setdefault(
                    req.spec.service_class, {"ttft": [0, 0], "tpot": [0, 0]}
                )
                cls[kind][0] += 1
                totals[kind][0] += 1
                if value > target:
                    cls[kind][1] += 1
                    totals[kind][1] += 1
        budget = slo.budget

        def burn(pair) -> float:
            n, over = pair
            return min((over / n) / budget, BURN_CAP) if n else 0.0

        out = {
            cls: {k: round(burn(v), 4) for k, v in row.items()}
            for cls, row in per_class.items()
        }
        return out, burn(totals["ttft"]), burn(totals["tpot"])

    def _burning_classes(self) -> frozenset:
        by_class, _, _ = self._burns()
        return frozenset(
            cls for cls, row in by_class.items()
            if any(b >= 1.0 for b in row.values())
        )

    # -- scheduling arms (engine semantics, transition-for-transition) ------

    def _rank_queue(self) -> List[int]:
        burning = self._burning_classes() if not self._fifo else frozenset()
        queued = [
            QueuedRequest(
                rid=r.rid, service_class=r.spec.service_class,
                tenant=r.spec.tenant, tokens=r.seq_len, position=i,
            )
            for i, r in enumerate(self._queue)
        ]
        # the same tiered ranking TablePolicy runs live (rank tier ->
        # tenant stride -> FCFS), via the shared classmethod so the
        # calibration test pins one implementation, not two
        from neuronx_distributed_llama3_2_tpu.serving.scheduler import (
            rank_queue,
        )

        return rank_queue(
            queued,
            lambda cls: self.vec.rank(cls, cls in burning),
            tenant_weights={},
        )

    def _reorder_queue(self, order: Sequence[int]) -> None:
        by_rid = {r.rid: r for r in self._queue}
        ranked = [by_rid.pop(rid) for rid in order if rid in by_rid]
        self._queue = ranked + [r for r in self._queue if r.rid in by_rid]

    def _admit(self) -> None:
        if not (self._queue and self._free_lanes):
            return
        if not self._fifo and len(self._queue) > 1:
            self._reorder_queue(self._rank_queue())
        lanes_before = set(self._active)
        self._admit_wave()
        self._emit(
            ActionType.ADMIT,
            lanes=sorted(set(self._active) - lanes_before),
            waiting=len(self._queue),
        )

    def _admit_wave(self) -> None:
        bs = self.w.block_size
        chunk = self.w.prefill_chunk_tokens
        while self._queue and self._free_lanes:
            req = self._queue[0]
            seq_len = req.seq_len  # resume re-prefills generated tokens
            n_total = _ceil_div(seq_len, bs)
            need_new = n_total + self.w.decode_reserve_blocks
            if self._free_blocks < need_new:
                return  # FCFS head-of-line: wait for blocks to drain
            self._queue.pop(0)
            lane = self._free_lanes.pop(0)
            req.lane = lane
            req.blocks = n_total
            self._free_blocks -= n_total
            self._active[lane] = req
            self._admission_order.append(req.rid)
            if chunk and seq_len > chunk:
                req.prefilling = True
                req.prefill_pos = 0
                req.prefill_target = seq_len
                self._frontier[lane] = 0
                self._dirty_lanes.add(lane)
                continue
            # whole-suffix admission prefill (no PREFILL_CHUNK action —
            # the wave's single ADMIT record covers it, as live)
            bucket = pick_bucket(self.w.prefill_buckets, max(seq_len, 1))
            self._charge(
                ("pctx", bucket, "sim", False), bucket - max(seq_len, 1),
                "prefill",
            )
            req.position = seq_len
            self._commit_token(req)
            self._frontier[lane] = req.position
            self._dirty_lanes.add(lane)
            self._maybe_finish(req)

    def _advance_prefills(self, budget_tokens: Optional[int]) -> None:
        chunk = self.w.prefill_chunk_tokens
        spent = 0
        for lane, req in list(self._active.items()):
            if not req.prefilling:
                continue
            if (
                budget_tokens is not None
                and spent > 0
                and spent >= budget_tokens
            ):
                break
            start = req.prefill_pos
            piece = min(chunk, req.prefill_target - start)
            final = start + piece >= req.prefill_target
            bucket = pick_bucket(self.w.prefill_buckets, max(piece, 1))
            if start == 0:
                self._charge(
                    ("pctx", bucket, "sim", False), bucket - max(piece, 1),
                    "prefill",
                )
            else:
                kv_limit = self._kv_bucket(
                    min(start + bucket, self.w.max_seq_len)
                )
                self._charge(
                    ("psfx", bucket, kv_limit, "sim", False),
                    bucket - max(piece, 1), "prefill",
                )
            req.prefill_pos = start + piece
            spent += piece
            self._emit(
                ActionType.PREFILL_CHUNK, rid=req.rid, lane=lane,
                tokens=piece, final=final,
            )
            if not final:
                continue
            req.prefilling = False
            req.position = req.prefill_target
            self._commit_token(req)
            self._frontier[lane] = req.position
            self._dirty_lanes.add(lane)
            self._maybe_finish(req)

    def _ensure_decode_blocks(self) -> None:
        bs = self.w.block_size
        for lane in sorted(self._active, key=lambda l: self._active[l].rid):
            req = self._active.get(lane)
            if req is None or req.prefilling:
                continue
            if self._frontier[lane] // bs < req.blocks:
                continue
            while True:
                if self._free_blocks > 0:
                    self._free_blocks -= 1
                    req.blocks += 1
                    self._table_deltas += 1
                    break
                victim = max(self._active.values(), key=lambda r: r.rid)
                self._preempt(victim)
                if victim is req:
                    break

    def _decode_ready(self) -> List[int]:
        return [l for l, r in self._active.items() if not r.prefilling]

    def _dispatch_sync_decode(self) -> None:
        if not self._decode_ready():
            return
        self._ensure_decode_blocks()
        lanes = self._decode_ready()
        if not lanes:
            return
        self._flush()
        kv_need = max(self._frontier[l] for l in lanes) + 1
        kv_limit = self._kv_bucket(kv_need)
        self._charge(
            ("pdecode", "sim", kv_limit, False, False),
            kv_limit - kv_need, "decode",
        )
        self._emit(
            ActionType.DECODE_DISPATCH, mode="sync", lanes=list(lanes),
            kv=kv_limit,
        )
        for lane in lanes:
            self._frontier[lane] += 1
        self._apply_readback(lanes, lag=0)

    def _apply_readback(self, lanes: List[int], lag: int) -> None:
        """Sim twin of ``_read_and_apply``: commit one token per lane,
        then — if a lane finished while a lookahead is in flight — drain
        the lookahead as its lame-duck step (survivors get an ordinary
        decode token, dead lanes' post-finish tokens are discarded)."""
        finishing: List[_SimReq] = []
        for lane in lanes:
            req = self._active.get(lane)
            if req is None:
                continue  # lane torn down between dispatch and readback
            req.position += 1
            self._commit_token(req, cap_check=True)
            if self._finish_due(req):
                finishing.append(req)
        self._emit(ActionType.READBACK, lanes=list(lanes), lag=lag)
        if finishing and self._pending is not None:
            lanes2, self._pending = self._pending, None
            dead = {r.lane for r in finishing}
            for lane in lanes2:
                if lane in dead:
                    self._frontier[lane] -= 1
                    continue
                req = self._active[lane]
                req.position += 1
                self._commit_token(req, cap_check=True)
                if self._finish_due(req):
                    finishing.append(req)
            self._emit(
                ActionType.READBACK, lanes=list(lanes2), lag=0,
                lame_duck=True,
            )
        for req in finishing:
            self._maybe_finish(req)

    def _async_eligible(self) -> bool:
        """Sim twin of the live eligibility: no lane to admit into, no
        lane mid-prefill, and the token in flight is no lane's last by
        count (a queue with no free lane leaves the step eligible)."""
        if not self._active or (self._queue and self._free_lanes):
            return False
        if any(r.prefilling for r in self._active.values()):
            return False
        for lane in self._pending or ():
            req = self._active.get(lane)
            if req is not None and (
                req.out + 1 >= req.spec.max_new_tokens
                or req.position + 1 >= self.w.max_seq_len - 1
            ):
                return False
        return True

    def _ensure_decode_blocks_async(self) -> bool:
        bs = self.w.block_size
        for lane in sorted(self._active, key=lambda l: self._active[l].rid):
            req = self._active[lane]
            if req.prefilling:
                continue
            if self._frontier[lane] // bs < req.blocks:
                continue
            if self._free_blocks <= 0:
                return False  # pool dry: preemption needed -> sync arm
            self._free_blocks -= 1
            req.blocks += 1
            self._table_deltas += 1
        return True

    def _step_async_arm(self) -> bool:
        """Depth-1 lookahead: dispatch step N+1, then read step N back.
        Returns False when the pool is dry (live ``sync_fallbacks``)."""
        if not self._ensure_decode_blocks_async():
            return False
        self._flush()
        lanes = self._decode_ready()
        kv_need = max(self._frontier[l] for l in lanes) + 1
        kv_limit = self._kv_bucket(kv_need)
        self._charge(
            ("pdecode", "sim", kv_limit, False, False),
            kv_limit - kv_need, "decode",
        )
        self._emit(
            ActionType.DECODE_DISPATCH, mode="async", lanes=list(lanes),
            kv=kv_limit,
        )
        for lane in lanes:
            self._frontier[lane] += 1
        prev, self._pending = self._pending, list(lanes)
        self._step_async = True
        if prev is not None:
            # read the PREVIOUS dispatch back (lag 1); if a lane finished,
            # _apply_readback drains the just-dispatched step as its
            # lame-duck step
            self._apply_readback(prev, lag=1)
        return True

    def _drain_pending(self) -> None:
        if self._pending is None:
            return
        pend, self._pending = self._pending, None
        self._apply_readback(pend, lag=0)

    # -- prefill budget (TablePolicy's table-driven rule) -------------------

    def _budget(self) -> Optional[int]:
        if self._fifo:
            return None
        _, ttft_burn, tpot_burn = self._burns()
        if ttft_burn >= 1.0:
            state = "ttft_burn"
        elif tpot_burn >= 1.0:
            state = "tpot_burn"
        else:
            state = "calm"
        return self.vec.budget_for(state)

    # -- the step loop ------------------------------------------------------

    def _arrive(self) -> None:
        while (
            self._arrived < len(self._arrivals)
            and self._arrivals[self._arrived].spec.submitted_step
            <= self._step
        ):
            req = self._arrivals[self._arrived]
            req.submitted_ms = self._clock_ms
            self._queue.append(req)
            self._arrived += 1

    def step(self) -> bool:
        self._arrive()
        self._step += 1
        self._step_host_ms = 0.0
        self._step_device_ms = 0.0
        self._step_async = False
        if (
            self.vec.prefer_async
            and self._async_eligible()
            and self._step_async_arm()
        ):
            pass  # pure lookahead step: no admit / prefill arms
        else:
            self._drain_pending()  # READBACK (emits only when pending)
            self._admit()
            self._advance_prefills(self._budget())
            self._dispatch_sync_decode()
        # async overlaps host scheduling with device compute; the sync
        # arms serialize them
        if self._step_async:
            self._clock_ms += max(self._step_device_ms, self._step_host_ms)
        else:
            self._clock_ms += self._step_device_ms + self._step_host_ms
        return bool(
            self._active or self._queue or self._arrived < len(self._arrivals)
        )

    def run(self, max_steps: int = 100_000) -> SimResult:
        while self.step():
            if self._step >= max_steps:
                self.findings.append(Finding(
                    rule=GC011, where="simulator",
                    message=f"workload did not drain in {max_steps} steps",
                    hint="raise max_steps or check the workload geometry",
                    detail=f"queue={len(self._queue)} active={len(self._active)}",
                ))
                break
        self._drain_pending()
        by_class, _, _ = self._burns()
        per_class_tokens: Dict[str, int] = {}
        ttft: Dict[int, float] = {}
        tpot: Dict[int, float] = {}
        for req in self._reqs:
            cls = req.spec.service_class
            per_class_tokens[cls] = per_class_tokens.get(cls, 0) + req.out
            if req.first_token_ms is not None:
                ttft[req.rid] = round(
                    req.first_token_ms - req.submitted_ms, 6
                )
            if req.finished_ms is not None and req.out > 1:
                tpot[req.rid] = round(
                    (req.finished_ms - req.first_token_ms) / (req.out - 1), 6
                )
        total_burn = sum(
            b for row in by_class.values() for b in row.values()
        )
        makespan = self._clock_ms
        objective = makespan * (1.0 + BURN_OBJECTIVE_WEIGHT * total_burn)
        return SimResult(
            steps=self._step,
            dispatches=self._dispatches,
            actions=self._actions,
            makespan_ms=round(makespan, 6),
            device_ms=round(self._device_ms, 6),
            host_ms=round(self._host_ms, 6),
            prefill_pad_tokens=self._prefill_pad,
            decode_pad_tokens=self._decode_pad,
            admission_order=list(self._admission_order),
            per_class_tokens=per_class_tokens,
            ttft_ms=ttft,
            tpot_ms=tpot,
            burn_by_class=by_class,
            objective=round(objective, 6),
            preemptions=self._preemptions,
            finished=sorted(self._finished),
            findings=list(self.findings),
        )


def simulate(
    workload: Workload,
    vector: Optional[PolicyVector] = None,
    max_steps: int = 100_000,
) -> SimResult:
    """Replay ``workload`` under ``vector`` (None = FIFO) and return the
    measured :class:`SimResult`."""
    return Simulator(workload, vector).run(max_steps=max_steps)


# -- the autotuner ----------------------------------------------------------


@dataclasses.dataclass
class SynthesisResult:
    best_vector: PolicyVector
    best: SimResult
    fifo: SimResult
    evaluated: int
    seed: int
    history: List[Tuple[str, float]]

    @property
    def improvement(self) -> float:
        """Fractional simulated-objective gain of the winner over FIFO
        (positive = the table beats FIFO on the recorded trace)."""
        if self.fifo.objective <= 0:
            return 0.0
        return (self.fifo.objective - self.best.objective) \
            / self.fifo.objective


def _vector_space(workload: Workload) -> Dict[str, List[Any]]:
    """Per-coordinate domains: every value is legal by construction
    (budgets are ladder rungs, weights are small floats)."""
    rungs = [int(b) for b in workload.prefill_buckets]
    budgets: List[Dict[str, int]] = [{}]
    for calm in rungs:
        budgets.append({
            "calm": calm, "ttft_burn": rungs[-1], "tpot_burn": rungs[0],
        })
    classes = workload.classes() or ["batch"]
    weights: List[Dict[str, float]] = [{}]
    for boosted in classes:
        weights.append({
            cls: (0.0 if cls == boosted else 1.0) for cls in classes
        })
    return {
        "class_weight": weights,
        "burn_boost": [0.0, 1.0, 2.0, 4.0],
        "prefill_budget": budgets,
        "verify_cadence": [1, 2, 4],
        "prefer_async": [True, False],
    }


def synthesize(
    workload: Workload,
    seed: int = 0,
    random_candidates: int = 8,
    descent_rounds: int = 1,
    max_steps: int = 100_000,
) -> SynthesisResult:
    """Search the :class:`PolicyVector` space over the simulator: seeded
    random sampling to land in a good basin, then coordinate descent
    (each coordinate swept over its typed domain, best kept) to polish.
    Deterministic for a given (workload, seed)."""
    import random as _random

    rng = _random.Random(seed)
    space = _vector_space(workload)
    fifo = simulate(workload, None, max_steps=max_steps)
    history: List[Tuple[str, float]] = [("fifo", fifo.objective)]
    evaluated = 1
    cache: Dict[str, float] = {}

    def score(vec: PolicyVector) -> float:
        nonlocal evaluated
        key = json.dumps(vec.to_dict(), sort_keys=True)
        if key not in cache:
            cache[key] = simulate(workload, vec, max_steps=max_steps).objective
            evaluated += 1
        return cache[key]

    best = PolicyVector(
        class_weight={
            cls: float(i)
            for i, cls in enumerate(
                sorted(
                    workload.classes(),
                    key=lambda c: {"interactive": 0}.get(c, 1),
                )
            )
        },
    )
    best_obj = score(best)
    history.append(("seeded", best_obj))
    for i in range(random_candidates):
        cand = PolicyVector(**{
            name: rng.choice(domain) for name, domain in space.items()
        })
        obj = score(cand)
        history.append((f"random{i}", obj))
        if obj < best_obj:
            best, best_obj = cand, obj
    for r in range(max(descent_rounds, 0)):
        improved = False
        for name, domain in space.items():
            for value in domain:
                cand = dataclasses.replace(best, **{name: value})
                obj = score(cand)
                if obj < best_obj - 1e-12:
                    best, best_obj = cand, obj
                    improved = True
        history.append((f"descent{r}", best_obj))
        if not improved:
            break
    final = simulate(workload, best, max_steps=max_steps)
    return SynthesisResult(
        best_vector=best, best=final, fifo=fifo,
        evaluated=evaluated, seed=seed, history=history,
    )


# -- policy table artifact --------------------------------------------------


def build_table(
    workload: Workload, synth: SynthesisResult
) -> dict:
    """Assemble the (uncertified) policy-table artifact: per-class
    entries of the winning vector + the three freshness fingerprints.
    ``certify_table`` stamps the explorer certificate in afterwards;
    ``table_id`` is recomputed on every stamp."""
    vec = synth.best_vector
    wd = workload.to_dict()
    classes = workload.classes() or ["batch"]
    body = {
        "version": 1,
        "generator": "graftplan",
        "seed": synth.seed,
        "ladder": {
            "prefill": [int(b) for b in workload.prefill_buckets],
            "kv": [int(b) for b in workload.kv_buckets],
        },
        "fingerprints": {
            "automaton": automaton_fingerprint(),
            "ladder": ladder_fingerprint(
                workload.prefill_buckets, workload.kv_buckets
            ),
            "trace": trace_fingerprint(wd),
        },
        "workload": {
            "requests": len(workload.requests),
            "classes": {
                cls: sum(
                    1 for r in workload.requests if r.service_class == cls
                )
                for cls in classes
            },
            "trace": dict(workload.trace),
        },
        "classes": {
            cls: {
                "weight": vec.rank(cls, burning=False),
                "burn_boost": vec.burn_boost,
            }
            for cls in classes
        },
        "prefill_budget": dict(vec.prefill_budget),
        "verify_cadence": vec.verify_cadence,
        "prefer_async": vec.prefer_async,
        "vector": vec.to_dict(),
        "objective": {
            "fifo": synth.fifo.objective,
            "table": synth.best.objective,
            "improvement": round(synth.improvement, 6),
            "evaluated": synth.evaluated,
            "simulated_burn_by_class": synth.best.burn_by_class,
            "fifo_burn_by_class": synth.fifo.burn_by_class,
        },
    }
    return _stamp(body)


def _stamp(body: dict) -> dict:
    body = dict(body)
    body.pop("table_id", None)
    body["table_id"] = _sha(body)
    return body


def certify_table(
    table: dict,
    engine_factory,
    max_steps: int = 200,
) -> dict:
    """Replay the candidate :class:`TablePolicy` live through the
    graftsched explorer harness — per-action automaton checks, invariant
    audits and the block-leak check on every transition — against a FIFO
    baseline of the same engine, and stamp the GC010-clean result (plus
    the stream-identity verdict) into the artifact. Needs a live CPU
    engine; everything else in this module is device-free."""
    from neuronx_distributed_llama3_2_tpu.analysis.graftsched import (
        _run_schedule,
    )
    from neuronx_distributed_llama3_2_tpu.serving.scheduler import (
        TablePolicy,
    )

    base = _run_schedule(engine_factory, None, "fifo", max_steps)
    policy = TablePolicy()
    policy.apply(table)
    cand = _run_schedule(engine_factory, policy, "table", max_steps)
    findings = list(base.findings) + list(cand.findings)
    cert = {
        "automaton_fingerprint": automaton_fingerprint(),
        "gc010_clean": not findings,
        "streams_match_fifo": cand.streams == base.streams,
        "schedules": 2,
        "steps": cand.steps,
        "actions": cand.actions,
        "findings": [f.format() for f in findings],
    }
    out = dict(table)
    out["certificate"] = cert
    return _stamp(out)


# -- GC011: load-time certificate / freshness checks ------------------------


class PolicyTableError(ValueError):
    """A policy table failed its GC011 load-time checks. ``findings``
    holds the structured rejection reasons."""

    def __init__(self, findings: List[Finding]) -> None:
        self.findings = list(findings)
        super().__init__(
            "policy table rejected (GC011):\n"
            + "\n".join(f.format() for f in findings)
        )


def check_policy_table(
    table: Mapping[str, Any],
    prefill_buckets: Optional[Sequence[int]] = None,
    kv_buckets: Optional[Sequence[int]] = None,
    suppress: Iterable[str] = (),
) -> List[Finding]:
    """GC011: audit a policy-table artifact for load. Checks, each named
    after the stale component in its finding:

    - ``certificate``: present, explorer-clean (``gc010_clean``), and
      stamped under the live automaton.
    - ``automaton``: the table's automaton fingerprint matches the live
      :data:`~.graftsched.AUTOMATON` edge table.
    - ``ladder``: the table's ladder fingerprint matches the live
      catalog ladders (checked when the caller passes them — the engine
      does; a bare ``SloPolicy.from_table`` checks against the table's
      own recorded ladder only).
    - ``budget``: every prefill chunk budget is a prefill-ladder rung.

    Returns findings (empty = clean); :func:`load_policy_table` raises
    :class:`PolicyTableError` on any."""
    findings: List[Finding] = []

    def add(where: str, message: str, hint: str, detail: str) -> None:
        if GC011 not in suppress:
            findings.append(Finding(
                rule=GC011, where=where, message=message, hint=hint,
                detail=detail,
            ))

    table_id = str(table.get("table_id", "?"))[:12]
    live_auto = automaton_fingerprint()
    cert = table.get("certificate")
    if not isinstance(cert, Mapping):
        add(
            f"table {table_id}",
            "policy table carries no explorer certificate",
            "re-synthesize with scripts/graftplan_gate.py --write-table "
            "(certify_table stamps the GC010-clean explorer result)",
            "certificate missing",
        )
        cert = None
    elif not cert.get("gc010_clean"):
        add(
            f"table {table_id}",
            "certificate records a GC010-unclean explorer run",
            "the candidate policy emitted an illegal schedule during "
            "certification; do not load this table",
            "certificate unclean",
        )
    if cert is not None and cert.get("automaton_fingerprint") != live_auto:
        add(
            f"table {table_id}",
            "certificate was stamped under a different automaton edge "
            "table — the stale component is the automaton",
            "the legality rules changed since certification; "
            "re-synthesize and re-certify",
            f"stale automaton certificate "
            f"{str(cert.get('automaton_fingerprint'))[:12]}",
        )
    fp = table.get("fingerprints") or {}
    if fp.get("automaton") != live_auto:
        add(
            f"table {table_id}",
            "table fingerprint does not match the live AUTOMATON edge "
            "table — the stale component is the automaton",
            "graftsched.AUTOMATON changed since this table was built; "
            "re-synthesize against the current rules",
            f"stale automaton fingerprint {str(fp.get('automaton'))[:12]}",
        )
    ladder = table.get("ladder") or {}
    table_prefill = [int(b) for b in ladder.get("prefill", [])]
    table_kv = [int(b) for b in ladder.get("kv", [])]
    if prefill_buckets is not None and kv_buckets is not None:
        live_ladder = ladder_fingerprint(prefill_buckets, kv_buckets)
        if fp.get("ladder") != live_ladder:
            add(
                f"table {table_id}",
                "table ladder fingerprint does not match the live "
                "catalog bucket ladders — the stale component is the "
                "ladder",
                "the engine's prefill/kv bucket ladders differ from the "
                "ones the table was synthesized against; re-synthesize "
                "on this engine's geometry",
                f"stale ladder fingerprint {str(fp.get('ladder'))[:12]}",
            )
        budget_ladder = [int(b) for b in prefill_buckets]
    else:
        budget_ladder = table_prefill
    if table_prefill and fp.get("ladder") != ladder_fingerprint(
        table_prefill, table_kv
    ):
        add(
            f"table {table_id}",
            "table ladder fingerprint does not cover its own recorded "
            "ladder — the artifact was hand-edited",
            "regenerate the artifact; fingerprints are stamped, never "
            "edited",
            "ladder fingerprint inconsistent",
        )
    for state, budget in (table.get("prefill_budget") or {}).items():
        if budget_ladder and int(budget) not in budget_ladder:
            add(
                f"table {table_id}",
                f"prefill chunk budget {budget} ({state}) is not a rung "
                f"of the prefill ladder {budget_ladder}",
                "budgets must quantize to catalog rungs or every "
                "budgeted wave compiles an out-of-catalog shape",
                f"out-of-ladder budget {state}={budget}",
            )
    return findings


def load_policy_table(
    source: Any,
    prefill_buckets: Optional[Sequence[int]] = None,
    kv_buckets: Optional[Sequence[int]] = None,
) -> dict:
    """Load a policy-table artifact (path or already-parsed dict) under
    GC011: any finding raises :class:`PolicyTableError`. Pass the live
    engine's ladders to also enforce ladder freshness (the engine's
    loader does)."""
    if isinstance(source, (str, bytes)):
        with open(source) as fh:
            table = json.load(fh)
    else:
        table = dict(source)
    findings = check_policy_table(
        table, prefill_buckets=prefill_buckets, kv_buckets=kv_buckets
    )
    if findings:
        raise PolicyTableError(findings)
    return table
