"""graftsched: schedule legality automaton + interleaving explorer.

The third analyzer beside shardlint (source ASTs) and graftcheck
(jaxprs/compiled programs): this one sees *schedules*. Since the
step-policy refactor (serving/policy.py) every engine step executes a
sequence of typed :class:`~..serving.policy.StepAction`\\ s and records
what actually ran — policy-scheduled phases plus the engine-internal
PREEMPT/FINISH/flush transitions — into ``engine.action_trace``. The
engine's core correctness claim is *schedule-invariance*: any legal
interleaving of commuting actions produces token-identical streams. This
module makes "legal" a static object and then model-checks it:

1. **Legality automaton** (:data:`AUTOMATON`, :func:`check_trace`): a
   small state machine over the action alphabet tracking the lookahead
   depth and the freed-lane set. The edges encode the ordering rules the
   engine's asserts and comments promise piecemeal:

   - VERIFY only with the lookahead drained (same-step readback).
   - LANE_SET_FLUSH only at pipeline-drained boundaries (full-lane syncs
     donate all residents); TABLE_DELTA_FLUSH is mid-flight-safe.
   - ADMIT / PREFILL_CHUNK only drained (both dirty-mark lanes, and the
     dirty flush asserts no step in flight).
   - READBACK lag <= 1 (depth-1 lookahead), never without a dispatch
     outstanding; DECODE_DISPATCH never beyond depth 1.
   - FINISH / PREEMPT (block release) only drained — releasing blocks
     with a lame-duck step in flight lets a later program recycle blocks
     whose KV writes have not landed.
   - no DECODE_DISPATCH / VERIFY into a lane freed by FINISH/PREEMPT and
     not re-admitted (the host-state race behind rule GC010's name).

2. **Explorer** (:func:`explore`): drives fresh engines through seeded
   permutations of *commuting* action orders (swap ADMIT/PREFILL_CHUNK,
   force the sync path at async-eligible steps, insert redundant drains
   and AUDITs), asserting after every transition that ``audit_engine``
   and ``leak_check`` are clean and the automaton accepts, and at the end
   that terminal streams are identical across every explored schedule.
   Candidate schedules whose differing choices land only on statically
   independent (no-op or read-only) decision points are pruned without
   running — a sleep-set-style reduction over the commuting alphabet.

3. **Seeded mutations** (:func:`run_seeded_mutations`): re-introduce two
   historical ordering bugs into a recorded trace — block release before
   the lame-duck drain, and a full-lane sync mid-pipeline — and check the
   automaton rejects both (the model checker's own regression test).

Rule GC010 (graftcheck's catalogue) is :func:`check_action_trace`:
replay an engine's recorded trace through the automaton at teardown,
the same way ``audit_programs`` replays its registry. Host-only: this
module never imports jax — traces are plain host records.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from neuronx_distributed_llama3_2_tpu.serving.policy import (
    ActionType,
    StepAction,
    StepPolicy,
)

__all__ = [
    "AUTOMATON",
    "Finding",
    "KNOWN_MUTATIONS",
    "ScheduleState",
    "SeededSchedulePolicy",
    "check_action_trace",
    "check_flat",
    "check_trace",
    "explore",
    "flatten_trace",
    "run_seeded_mutations",
]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One legality violation at one trace position. Mirrors
    graftcheck's Finding (rule / locator / message / hint) with the
    program label replaced by a ``step:action`` locator."""

    rule: str
    where: str  # "step 12 action 3: DECODE_DISPATCH[async]"
    message: str
    hint: str
    detail: str = ""

    @property
    def fingerprint(self) -> str:
        digest = hashlib.sha1(
            f"{self.rule}|{self.where}|{self.detail}".encode()
        ).hexdigest()
        return digest[:12]

    def format(self) -> str:
        return (
            f"{self.where}: {self.rule} {self.message}\n"
            f"    hint: {self.hint}"
        )


#: The legality machine as a readable edge table (docs/static_analysis.md
#: renders this verbatim). ``guard`` is over the automaton state
#: (``outstanding`` = dispatched-but-unread decode steps, ``freed`` = lanes
#: released since their last ADMIT); ``effect`` is the transition.
AUTOMATON: Tuple[Dict[str, str], ...] = (
    dict(action="ADMIT", guard="outstanding == 0",
         effect="admitted lanes leave the freed set"),
    dict(action="PREFILL_CHUNK", guard="outstanding == 0", effect="-"),
    dict(action="DECODE_DISPATCH", guard="outstanding <= 1; lanes not freed",
         effect="outstanding += 1"),
    dict(action="VERIFY",
         guard="outstanding == 0; lanes not freed; "
               "tree meta (nodes) within the lane draft budget",
         effect="- (same-step readback)"),
    dict(action="MIXED_DISPATCH", guard="outstanding == 0; lanes not freed",
         effect="- (same-step readback)"),
    dict(action="READBACK", guard="outstanding >= 1; lag <= 1",
         effect="outstanding -= 1"),
    dict(action="LANE_SET_FLUSH", guard="outstanding == 0", effect="-"),
    dict(action="TABLE_DELTA_FLUSH", guard="always legal", effect="-"),
    dict(action="PREEMPT", guard="outstanding == 0",
         effect="lane joins the freed set"),
    dict(action="FINISH", guard="outstanding == 0",
         effect="lane joins the freed set"),
    dict(action="RESTORE", guard="outstanding == 0; lanes not freed",
         effect="- (spilled blocks upload into fresh pool ids)"),
    dict(action="AUDIT", guard="always legal", effect="-"),
)

_HINTS = {
    "verify-in-flight": (
        "verify needs same-step readback; drain the lookahead (READBACK) "
        "before scheduling VERIFY"
    ),
    "mixed-in-flight": (
        "the fused mixed-mode step reads back in the same step and its "
        "prefill rows rewrite live KV rows; drain the lookahead "
        "(READBACK) before scheduling MIXED_DISPATCH"
    ),
    "lane-set-in-flight": (
        "full-lane syncs donate all residents; only flush dirty lanes at "
        "a pipeline-drained boundary"
    ),
    "sched-in-flight": (
        "admission/prefill dirty-mark lanes whose flush requires no step "
        "in flight; drain first"
    ),
    "release-in-flight": (
        "releasing blocks with a step in flight lets a later program "
        "recycle rows whose KV writes have not landed (the lame-duck "
        "drain bug); drain before FINISH/PREEMPT"
    ),
    "lag": (
        "the lookahead pipeline is depth-1: every dispatch must be read "
        "back within one further dispatch"
    ),
    "freed-lane": (
        "the lane was released (FINISH/PREEMPT) and not re-admitted; "
        "dispatching into it races host teardown against device writes"
    ),
    "restore-in-flight": (
        "a tiered-KV restore scatters into freshly allocated pool blocks; "
        "with a step in flight those allocations could recycle blocks "
        "whose KV writes have not landed — restores ride the drained "
        "admission wave only"
    ),
    "bookkeeping": (
        "the recorded trace is internally inconsistent — an emission "
        "site is missing or double-counted in serving/engine.py"
    ),
    "tree-meta": (
        "a tree VERIFY record must carry a node count consistent with "
        "its lane set and rung width (each lane offers at most k packed "
        "draft nodes); an out-of-range count means the packed payload "
        "build and the action emission disagree in serving/engine.py"
    ),
}


@dataclasses.dataclass
class ScheduleState:
    """Automaton state threaded through a replay."""

    outstanding: int = 0          # dispatched-but-unread decode steps
    freed: set = dataclasses.field(default_factory=set)

    def copy(self) -> "ScheduleState":
        return ScheduleState(self.outstanding, set(self.freed))


def _finding(rule_key: str, where: str, message: str, detail: str = "") -> Finding:
    return Finding(
        rule="GC010", where=where, message=message,
        hint=_HINTS[rule_key], detail=detail or message,
    )


def advance(state: ScheduleState, act: StepAction, where: str) -> List[Finding]:
    """Advance the automaton by one action, returning violations (the
    state advances regardless, so one bad transition does not cascade
    into spurious downstream findings)."""
    v: List[Finding] = []
    t = act.type
    meta = act.meta or {}
    lanes = list(meta.get("lanes") or [])
    if t is ActionType.ADMIT:
        if state.outstanding:
            v.append(_finding(
                "sched-in-flight", where,
                f"ADMIT with {state.outstanding} step(s) in flight",
            ))
        state.freed -= set(lanes)
    elif t is ActionType.PREFILL_CHUNK:
        if state.outstanding:
            v.append(_finding(
                "sched-in-flight", where,
                f"PREFILL_CHUNK with {state.outstanding} step(s) in flight",
            ))
    elif t is ActionType.DECODE_DISPATCH:
        if state.outstanding > 1:
            v.append(_finding(
                "lag", where,
                f"dispatch at lookahead depth {state.outstanding} "
                "(depth-1 pipeline)",
            ))
        hit = sorted(set(lanes) & state.freed)
        if hit:
            v.append(_finding(
                "freed-lane", where,
                f"decode dispatch into freed lane(s) {hit}",
                detail=f"lanes={hit}",
            ))
        state.outstanding += 1
    elif t is ActionType.VERIFY:
        if state.outstanding:
            v.append(_finding(
                "verify-in-flight", where,
                f"VERIFY with {state.outstanding} step(s) in flight",
            ))
        hit = sorted(set(lanes) & state.freed)
        if hit:
            v.append(_finding(
                "freed-lane", where,
                f"verify dispatch into freed lane(s) {hit}",
                detail=f"lanes={hit}",
            ))
        if meta.get("tree"):
            nodes = meta.get("nodes")
            k = int(meta.get("k", 0) or 0)
            cap = len(lanes) * max(k, 0)
            if not isinstance(nodes, int) or not 0 <= nodes <= cap:
                v.append(_finding(
                    "tree-meta", where,
                    f"tree VERIFY node count {nodes!r} outside "
                    f"[0, {cap}] (lanes={len(lanes)}, k={k})",
                    detail=f"nodes={nodes!r} cap={cap}",
                ))
    elif t is ActionType.MIXED_DISPATCH:
        if state.outstanding:
            v.append(_finding(
                "mixed-in-flight", where,
                f"MIXED_DISPATCH with {state.outstanding} step(s) in flight",
            ))
        packed = set(lanes) | set(meta.get("prefill_lanes") or [])
        hit = sorted(packed & state.freed)
        if hit:
            v.append(_finding(
                "freed-lane", where,
                f"mixed dispatch into freed lane(s) {hit}",
                detail=f"lanes={hit}",
            ))
    elif t is ActionType.READBACK:
        if state.outstanding < 1:
            v.append(_finding(
                "bookkeeping", where, "READBACK with nothing outstanding",
            ))
        else:
            state.outstanding -= 1
        lag = int(meta.get("lag", 0))
        if lag > 1:
            v.append(_finding(
                "lag", where, f"readback lag {lag} > 1",
                detail=f"lag={lag}",
            ))
    elif t is ActionType.LANE_SET_FLUSH:
        if state.outstanding:
            v.append(_finding(
                "lane-set-in-flight", where,
                f"full-lane sync with {state.outstanding} step(s) in flight",
            ))
    elif t is ActionType.TABLE_DELTA_FLUSH:
        pass  # single-entry deltas donate only the tables array
    elif t in (ActionType.PREEMPT, ActionType.FINISH):
        if state.outstanding:
            v.append(_finding(
                "release-in-flight", where,
                f"{t.value} (block release) with {state.outstanding} "
                "step(s) in flight",
            ))
        lane = meta.get("lane")
        if lane is not None:
            state.freed.add(lane)
    elif t is ActionType.RESTORE:
        if state.outstanding:
            v.append(_finding(
                "restore-in-flight", where,
                f"RESTORE with {state.outstanding} step(s) in flight",
            ))
        hit = sorted(set(lanes) & state.freed)
        if hit:
            v.append(_finding(
                "freed-lane", where,
                f"restore into freed lane(s) {hit}",
                detail=f"lanes={hit}",
            ))
    elif t is ActionType.AUDIT:
        pass
    return v


def check_flat(
    actions: Sequence[StepAction],
    start_outstanding: int = 0,
    label: str = "trace",
) -> List[Finding]:
    """Replay a flat action list through the automaton."""
    state = ScheduleState(outstanding=start_outstanding)
    v: List[Finding] = []
    for i, act in enumerate(actions):
        v.extend(advance(state, act, f"{label} action {i}: {act!r}"))
    return v


def check_trace(
    trace: Iterable[Tuple[int, bool, Sequence[StepAction]]],
) -> List[Finding]:
    """Replay an engine-format trace (per-step ``(step_index,
    pending_at_start, actions)`` entries, as ``engine.action_trace``
    holds). The first retained entry seeds the lookahead depth (the ring
    buffer may have dropped earlier steps); every later entry's recorded
    depth is cross-checked against the model — a mismatch means an
    emission site is missing, which would quietly blind the other rules."""
    v: List[Finding] = []
    state: Optional[ScheduleState] = None
    for step_index, pending_at_start, actions in trace:
        depth = 1 if pending_at_start else 0
        if state is None:
            state = ScheduleState(outstanding=depth)
        elif state.outstanding != depth:
            v.append(_finding(
                "bookkeeping", f"step {step_index}",
                f"recorded lookahead depth {depth} != modeled "
                f"{state.outstanding}",
            ))
            state.outstanding = depth  # resync; keep later findings honest
        for i, act in enumerate(actions):
            v.extend(advance(
                state, act, f"step {step_index} action {i}: {act!r}"
            ))
    return v


def check_action_trace(engine, suppress: Sequence[str] = ()) -> List[Finding]:
    """Rule GC010: replay ``engine.action_trace`` against the legality
    automaton — the teardown twin of graftcheck's ``audit_programs``.
    Returns findings ([] = accepted); ``suppress={"GC010"}`` silences it
    (per-rule, matching the graftcheck convention)."""
    if "GC010" in suppress:
        return []
    v = check_trace(engine.action_trace)
    # terminal consistency: after the last retained step the modeled
    # depth must match the engine's live pipeline state
    if engine.action_trace:
        state = ScheduleState(
            outstanding=1 if engine.action_trace[0][1] else 0
        )
        for _, _, actions in engine.action_trace:
            for act in actions:
                advance(state, act, "")
        live = 1 if engine._pending is not None else 0
        if state.outstanding != live:
            v.append(_finding(
                "bookkeeping", "trace end",
                f"modeled lookahead depth {state.outstanding} != live "
                f"engine depth {live}",
            ))
    return v


# ---------------------------------------------------------------------------
# Seeded mutations: the model checker's own regression tests
# ---------------------------------------------------------------------------


def _mutate_release_before_drain(
    actions: List[StepAction], rng: random.Random,
) -> Optional[List[StepAction]]:
    """Re-introduce the block-release-before-lame-duck-drain bug: move a
    FINISH to just before the READBACK that (in the recorded schedule)
    retired the step still in flight at that point."""
    sites = []
    for j, act in enumerate(actions):
        if act.type is not ActionType.FINISH:
            continue
        prior = [i for i in range(j) if actions[i].type is ActionType.READBACK]
        if prior:
            sites.append((prior[-1], j))
    if not sites:
        return None
    i, j = rng.choice(sites)
    out = list(actions)
    fin = out.pop(j)
    out.insert(i, fin)
    return out


def _mutate_lane_set_mid_pipeline(
    actions: List[StepAction], rng: random.Random,
) -> Optional[List[StepAction]]:
    """Re-introduce the lane_set-mid-pipeline bug: insert a full-lane
    sync right after a decode dispatch, while the dispatched step is
    still unread."""
    sites = [
        i for i, act in enumerate(actions)
        if act.type is ActionType.DECODE_DISPATCH
    ]
    if not sites:
        return None
    i = rng.choice(sites)
    out = list(actions)
    out.insert(i + 1, StepAction(
        ActionType.LANE_SET_FLUSH,
        meta={"lanes": list(actions[i].meta.get("lanes", [])), "in_flight": True},
    ))
    return out


#: name -> mutation over a flat action list (None when the trace has no
#: applicable site). Both are historical ordering bugs the automaton
#: exists to make unrepresentable.
KNOWN_MUTATIONS: Dict[str, Callable] = {
    "release-before-lame-duck-drain": _mutate_release_before_drain,
    "lane-set-mid-pipeline": _mutate_lane_set_mid_pipeline,
}


def flatten_trace(trace) -> Tuple[int, List[StepAction]]:
    """Flatten an engine-format trace to ``(start_outstanding, actions)``."""
    flat: List[StepAction] = []
    start = 0
    for idx, (_, pending_at_start, actions) in enumerate(trace):
        if idx == 0:
            start = 1 if pending_at_start else 0
        flat.extend(actions)
    return start, flat


def run_seeded_mutations(trace, seed: int = 0) -> Dict[str, List[Finding]]:
    """Apply every known mutation to a recorded trace and replay each
    mutant. Returns name -> findings; an empty list for any mutation
    means the automaton FAILED to catch that bug class (callers assert
    non-empty). Raises if the trace has no applicable mutation site —
    the caller's workload is too thin to certify anything."""
    start, flat = flatten_trace(trace)
    out: Dict[str, List[Finding]] = {}
    for name, fn in KNOWN_MUTATIONS.items():
        mutant = fn(flat, random.Random(seed))
        if mutant is None:
            raise ValueError(
                f"trace has no applicable site for mutation {name!r} "
                "(workload too thin: needs finishes and dispatches)"
            )
        out[name] = check_flat(
            mutant, start_outstanding=start, label=f"mutant[{name}]"
        )
    return out


# ---------------------------------------------------------------------------
# The bounded systematic explorer
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Choices:
    """Per-step schedule decisions a seeded run draws from its vector."""

    swap: bool = False        # PREFILL_CHUNK before ADMIT
    force_sync: bool = False  # decline the async dispatch this step
    extra_drain: bool = False  # redundant READBACK before dispatch (no-op)
    audit: bool = False       # interleave an AUDIT action


class SeededSchedulePolicy(StepPolicy):
    """FifoPolicy's action set with seeded permutations of the commuting
    decisions: ADMIT/PREFILL_CHUNK order, sync-instead-of-async at
    eligible steps, redundant drains, interleaved audits. Spec arms are
    not permuted (the explorer workloads run spec-off; verify ordering
    is covered by the automaton fixtures and the mutation mode)."""

    name = "graftsched-seeded"

    def __init__(self, vector: Sequence[_Choices]) -> None:
        self._vector = list(vector)
        self._step = 0

    def reset(self) -> None:
        self._step = 0

    def actions(self, view):
        c = (
            self._vector[self._step]
            if self._step < len(self._vector) else _Choices()
        )
        self._step += 1
        async_on = view.degrade_level < 2
        if async_on and view.async_eligible and not c.force_sync:
            yield StepAction(ActionType.DECODE_DISPATCH, mode="async")
            if not view.last_async_fell_back:
                return
        yield StepAction(ActionType.READBACK)
        if c.audit:
            yield StepAction(ActionType.AUDIT)
        first, second = (
            (ActionType.PREFILL_CHUNK, ActionType.ADMIT) if c.swap
            else (ActionType.ADMIT, ActionType.PREFILL_CHUNK)
        )
        yield StepAction(first)
        yield StepAction(second)
        if c.extra_drain:
            yield StepAction(ActionType.READBACK)  # drained: a no-op
        yield StepAction(ActionType.DECODE_DISPATCH, mode="sync")


@dataclasses.dataclass
class ScheduleReport:
    label: str
    steps: int
    actions: int
    findings: List[Finding]
    streams: Dict[int, tuple]
    trace: List[Tuple[int, bool, List[StepAction]]]


@dataclasses.dataclass
class ExplorationReport:
    baseline: ScheduleReport
    explored: List[ScheduleReport]
    pruned: int
    mismatches: List[str]

    @property
    def ok(self) -> bool:
        return (
            not self.mismatches
            and not self.baseline.findings
            and all(not r.findings for r in self.explored)
        )

    def summary(self) -> str:
        total = 1 + len(self.explored)
        bad = sum(
            1 for r in [self.baseline, *self.explored] if r.findings
        )
        return (
            f"{total} schedule(s) run, {self.pruned} pruned "
            f"(sleep-set), {bad} with violations, "
            f"{len(self.mismatches)} stream mismatch(es)"
        )


def _run_schedule(
    engine_factory: Callable[[Optional[StepPolicy]], Any],
    policy: Optional[StepPolicy],
    label: str,
    max_steps: int,
) -> ScheduleReport:
    """Run one engine to completion under one schedule, auditing after
    every recorded action: host invariants (audit_engine), pool leaks
    (leak_check) and the legality automaton, all incrementally."""
    from neuronx_distributed_llama3_2_tpu.serving.invariants import (
        audit_engine,
    )

    eng = engine_factory(policy)
    findings: List[Finding] = []
    state = ScheduleState()
    n_actions = 0

    def on_action(e, act: StepAction) -> None:
        nonlocal n_actions
        n_actions += 1
        where = f"{label} step {e._step_index} action: {act!r}"
        findings.extend(advance(state, act, where))
        for s in audit_engine(e):
            findings.append(Finding(
                "GC010", where, f"audit_engine: {s}",
                hint="engine invariant broken mid-schedule", detail=s,
            ))
        for bid in e.allocator.leak_check():
            findings.append(Finding(
                "GC010", where, f"leak_check: block {bid}",
                hint="pool partition broken mid-schedule",
                detail=f"block={bid}",
            ))

    eng._on_action = on_action
    steps = 0
    while eng.step():
        steps += 1
        if steps >= max_steps:
            findings.append(Finding(
                "GC010", f"{label} step {steps}",
                f"schedule did not complete within {max_steps} steps",
                hint="workload/step budget mismatch or a livelocked schedule",
            ))
            break
    streams = {
        rid: tuple(r.out) for rid, r in eng._finished.items()
    }
    return ScheduleReport(
        label=label, steps=steps, actions=n_actions,
        findings=findings, streams=streams,
        trace=[(i, p, list(a)) for i, p, a in eng.action_trace],
    )


def explore(
    engine_factory: Callable[[Optional[StepPolicy]], Any],
    *,
    schedules: int = 6,
    candidates: int = 64,
    horizon: int = 64,
    max_steps: int = 200,
    seed: int = 0,
) -> ExplorationReport:
    """Bounded systematic exploration. ``engine_factory(policy)`` must
    return a fresh engine with its workload already submitted (policy
    None = the engine default, the baseline FifoPolicy run).

    Candidate choice vectors are drawn from ``seed``; before running one,
    its decisions are projected onto the *effective* decision points
    observed in the baseline trace (steps where both admission and
    prefill did work, steps that dispatched async) — vectors that differ
    only at ineffective points (no-op drains, read-only audits, swaps at
    steps where one side was idle) are pruned without running, the
    sleep-set reduction over this commuting alphabet."""
    baseline = _run_schedule(engine_factory, None, "fifo", max_steps)

    # effective decision points, from the baseline schedule's trace shape:
    # steps are labelled 1.. by the engine; vectors are 0-indexed by step
    swap_steps: set = set()
    async_steps: set = set()
    for step_index, _, actions in baseline.trace:
        kinds = {}
        for act in actions:
            kinds.setdefault(act.type, []).append(act)
        admits = kinds.get(ActionType.ADMIT, [])
        admitted = any(a.meta.get("lanes") for a in admits)
        prefilled = ActionType.PREFILL_CHUNK in kinds
        if admitted and prefilled:
            swap_steps.add(step_index - 1)
        if any(
            a.mode == "async"
            for a in kinds.get(ActionType.DECODE_DISPATCH, [])
        ):
            async_steps.add(step_index - 1)

    rng = random.Random(seed)
    seen: set = set()
    explored: List[ScheduleReport] = []
    pruned = 0
    for cand in range(candidates):
        if len(explored) >= schedules:
            break
        vector = [
            _Choices(
                swap=rng.random() < 0.5,
                force_sync=rng.random() < 0.35,
                extra_drain=rng.random() < 0.3,
                audit=rng.random() < 0.25,
            )
            for _ in range(horizon)
        ]
        projection = (
            tuple(sorted(s for s in swap_steps if vector[s].swap)),
            tuple(sorted(s for s in async_steps if vector[s].force_sync)),
        )
        if projection in seen:
            pruned += 1
            continue
        seen.add(projection)
        explored.append(_run_schedule(
            engine_factory, SeededSchedulePolicy(vector),
            f"seed{seed}/cand{cand}", max_steps,
        ))

    mismatches: List[str] = []
    for rep in explored:
        if rep.streams != baseline.streams:
            diff = [
                rid for rid in set(baseline.streams) | set(rep.streams)
                if baseline.streams.get(rid) != rep.streams.get(rid)
            ]
            mismatches.append(
                f"{rep.label}: terminal streams diverge from fifo on "
                f"rid(s) {sorted(diff)}"
            )
    return ExplorationReport(
        baseline=baseline, explored=explored,
        pruned=pruned, mismatches=mismatches,
    )
