"""SLO-aware step schedulers for the paged engine (graftserve).

Two non-FIFO :class:`~.policy.StepPolicy` implementations live here —
:class:`SloPolicy` (hand-tuned heuristics over live burn gauges, ROADMAP
item 2) and :class:`TablePolicy` (constants synthesized offline by
analysis/graftplan.py and loaded from a certified policy-table
artifact). Both keep the FIFO schedule *shape* — the exact arm structure
the GC010 legality automaton was built against — and move all of their
scheduling authority into the two pieces of ``StepAction`` meta the
engine honors:

- ``ADMIT meta["admit_order"]``: a ranking of the waiting queue. The
  admission wave itself is unchanged (strict head-of-line over the
  reordered queue, identical block accounting), but *which* request sits
  at the head is a policy decision built from three signals:

  1. **Service class** — ``interactive`` (TTFT-sensitive) ranks ahead of
     ``batch`` (throughput). A request's class is declared at
     ``submit(service_class=...)`` and never touches the device path.
  2. **Burn-rate feedback** — the per-class burn gauges the
     :class:`~.slo.SLOMonitor` maintains (``metrics.slo_burn_by_class``).
     A class burning its error budget gets a priority boost: admission
     shifts *away from the classes meeting their objectives* toward the
     burning one until its windowed burn drops back under the threshold.
  3. **Tenant fairness** — within a priority tier, requests interleave
     across tenants by weighted round-robin (stride scheduling over
     ``tenant_weights``, default weight 1), FCFS within a tenant. A
     chatty tenant cannot monopolize an admission wave.

- ``PREFILL_CHUNK meta["budget_tokens"]``: an aggregate chunked-prefill
  token budget per step, quantized against the catalog's prefill bucket
  ladder and steered by the graftmeter pad-waste rungs (the budget rung
  is the largest bucket whose observed pad fraction stays under
  ``pad_waste_ceiling``). Global burn gauges bend it: TTFT burning →
  widen the budget (drain queued prefills faster); TPOT burning → clamp
  to the smallest rung (protect the decode cadence). The engine always
  advances at least one prefilling lane per wave, so a budget paces
  prefill but can never starve it.

Because every arm below is action-for-action the FIFO shape, every
schedule SloPolicy emits is GC010-legal by the same argument FIFO's are;
``scripts/graftsched_gate.py`` proves it anyway by replaying SloPolicy
traces under mixed-class traffic through the automaton and the explorer.
"""

from __future__ import annotations

import logging
from typing import Dict, Iterator, List, Mapping, Optional

from neuronx_distributed_llama3_2_tpu.serving.policy import (
    ActionType,
    EngineView,
    QueuedRequest,
    StepAction,
    StepPolicy,
    register_policy,
)

logger = logging.getLogger(__name__)

#: Admission priority per service class (lower = admitted earlier).
CLASS_RANK: Dict[str, int] = {"interactive": 0, "batch": 1}

#: Priority boost (rank subtraction) for a class burning its SLO budget.
#: 2 deliberately lifts a burning ``batch`` class above non-burning
#: ``interactive`` — burn feedback outranks the static tier.
BURN_BOOST = 2


def rank_queue(
    queued: List[QueuedRequest],
    rank_fn,
    tenant_weights: Optional[Mapping[str, float]] = None,
) -> List[int]:
    """THE admission-ranking kernel, shared by :class:`SloPolicy`,
    :class:`TablePolicy` and the graftplan simulator (the calibration
    test pins one implementation, not two): priority tiers from
    ``rank_fn(service_class)`` (lower admits earlier), weighted
    round-robin across tenants inside a tier (stride scheduling —
    each pick charges the tenant 1/weight), FCFS within a tenant.
    Deterministic: ties break on tenant name then queue position,
    never on iteration order."""
    weights = dict(tenant_weights or {})

    def weight(tenant: str) -> float:
        w = weights.get(tenant, 1.0)
        return w if w > 0 else 1.0

    tiers: Dict[float, Dict[str, List[QueuedRequest]]] = {}
    for q in queued:
        tiers.setdefault(rank_fn(q.service_class), {}) \
            .setdefault(q.tenant, []).append(q)
    order: List[int] = []
    for rank in sorted(tiers):
        by_tenant = tiers[rank]
        for reqs in by_tenant.values():
            reqs.sort(key=lambda q: q.position)  # FCFS within tenant
        credit = {t: 0.0 for t in by_tenant}
        while by_tenant:
            tenant = min(
                by_tenant,
                key=lambda t: (credit[t] / weight(t), t),
            )
            order.append(by_tenant[tenant].pop(0).rid)
            credit[tenant] += 1.0
            if not by_tenant[tenant]:
                del by_tenant[tenant]
    return order


@register_policy
class SloPolicy(StepPolicy):
    """SLO-aware scheduling over the policy seam (see module docstring).

    Construction knobs (all optional — ``make_policy("slo")`` /
    ``PagedConfig(step_policy="slo")`` use the defaults):

    - ``tenant_weights``: tenant → weight for the admission round-robin
      (unlisted tenants weigh 1.0; higher weight = more admission slots
      per wave).
    - ``burn_threshold``: windowed burn at or above which a class counts
      as burning (matches the SLOMonitor alert default of 1.0 — exactly
      consuming the error budget).
    - ``pad_waste_ceiling``: max observed pad fraction a prefill bucket
      rung may have and still be chosen as the per-step budget.
    """

    name = "slo"

    def __init__(
        self,
        tenant_weights: Optional[Mapping[str, float]] = None,
        burn_threshold: float = 1.0,
        pad_waste_ceiling: float = 0.5,
    ) -> None:
        self._spec_pause = 0
        self.tenant_weights = dict(tenant_weights or {})
        self.burn_threshold = float(burn_threshold)
        self.pad_waste_ceiling = float(pad_waste_ceiling)
        self._logged_catalog = False

    @classmethod
    def from_table(cls, source) -> "TablePolicy":
        """Build a table-driven policy from a graftplan policy-table
        artifact (path or dict). The table is GC011-checked against its
        own certificate and automaton fingerprint here; the engine
        re-checks ladder freshness against its live catalog when the
        policy is installed (``PagedConfig.policy_table_path`` or
        ``load_policy_table``)."""
        from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
            load_policy_table,
        )

        policy = TablePolicy()
        policy.apply(load_policy_table(source))
        return policy

    def reset(self) -> None:
        self._spec_pause = 0
        self._logged_catalog = False

    # -- admission ranking -------------------------------------------------

    def _burning_classes(self, view: EngineView) -> frozenset:
        burning = set()
        for cls, row in view.slo_burn_by_class.items():
            if any(b >= self.burn_threshold for b in row.values()):
                burning.add(cls)
        return frozenset(burning)

    def _rank(self, cls: str, burning: frozenset) -> int:
        rank = CLASS_RANK.get(cls, max(CLASS_RANK.values()) + 1)
        if cls in burning:
            rank -= BURN_BOOST
        return rank

    def _admit_order(self, view: EngineView) -> List[int]:
        """Rank the waiting queue through :func:`rank_queue`: priority
        tiers (class rank with burn boost), weighted round-robin across
        tenants inside a tier, FCFS inside a tenant."""
        burning = self._burning_classes(view)
        return rank_queue(
            view.queued(),
            lambda cls: self._rank(cls, burning),
            tenant_weights=self.tenant_weights,
        )

    def _admit_meta(self, view: EngineView) -> dict:
        # ranking a queue the wave cannot admit from is wasted O(n log n)
        # per step — a 10k-deep queue behind full lanes would make every
        # step quadratic-ish for nothing
        if view.queue_depth <= 1 or view.free_lanes == 0:
            return {}
        return {"admit_order": self._admit_order(view)}

    # -- chunked-prefill budget --------------------------------------------

    def _prefill_budget(self, view: EngineView) -> Optional[int]:
        buckets = view.prefill_buckets
        if not buckets:
            return None
        if not self._logged_catalog:
            self._logged_catalog = True
            logger.debug(
                "SloPolicy budget ladder:\n%s", view.catalog_description
            )
        pads = view.pad_by_rung("prefill")
        # the largest rung whose observed pad fraction stays under the
        # ceiling; unobserved rungs are assumed fine (nothing dispatched
        # into them yet, so no evidence of waste)
        best = buckets[0]
        for rung in buckets:
            row = pads.get(rung)
            if row is None:
                best = rung
                continue
            total = row.get("need_tokens", 0) + row.get("pad_tokens", 0)
            if not total or row.get("pad_tokens", 0) / total <= self.pad_waste_ceiling:
                best = rung
        budget = int(best)
        ttft_burn, tpot_burn = view.slo_burn
        if ttft_burn >= self.burn_threshold:
            budget *= 2                 # TTFT burning: drain prefills faster
        elif tpot_burn >= self.burn_threshold:
            budget = int(buckets[0])    # TPOT burning: protect decode cadence
        return budget

    def _prefill_meta(self, view: EngineView) -> dict:
        budget = self._prefill_budget(view)
        return {} if budget is None else {"budget_tokens": budget}

    # -- the schedule ------------------------------------------------------

    def actions(self, view: EngineView) -> Iterator[StepAction]:
        # action-for-action the FifoPolicy arm structure (GC010-legal by
        # construction); only the ADMIT / PREFILL_CHUNK meta differs
        cfg = view.config
        spec_on = view.spec_enabled and view.degrade_level < 1
        async_on = view.degrade_level < 2
        if spec_on and self._spec_pause <= 0:
            yield StepAction(ActionType.READBACK)
            yield StepAction(ActionType.ADMIT, meta=self._admit_meta(view))
            yield StepAction(
                ActionType.PREFILL_CHUNK, meta=self._prefill_meta(view)
            )
            yield StepAction(ActionType.VERIFY)
            if not view.last_verify_drafted:
                if async_on:
                    self._spec_pause = cfg.spec_retry_steps
                yield StepAction(ActionType.DECODE_DISPATCH, mode="sync")
            return
        if self._spec_pause > 0:
            self._spec_pause -= 1
        if async_on and view.async_eligible:
            yield StepAction(ActionType.DECODE_DISPATCH, mode="async")
            if not view.last_async_fell_back:
                return
        yield StepAction(ActionType.READBACK)
        yield StepAction(ActionType.ADMIT, meta=self._admit_meta(view))
        yield StepAction(
            ActionType.PREFILL_CHUNK, meta=self._prefill_meta(view)
        )
        yield StepAction(ActionType.DECODE_DISPATCH, mode="sync")


@register_policy
class TablePolicy(SloPolicy):
    """Policy driven by a graftplan-synthesized table
    (``step_policy="table"``; analysis/graftplan.py, docs/serving.md
    "Policy tables").

    Where :class:`SloPolicy` computes its admission ranks and prefill
    budgets from hand-tuned heuristics over live gauges, TablePolicy
    reads them from a certified offline artifact: per-class admission
    weights and burn boost, a prefill chunk budget per burn state
    (quantized to the catalog's prefill ladder), a verify cadence, and
    the sync/async preference. The arm *structure* stays action-for-
    action the FIFO shape, so every schedule is GC010-legal by the same
    argument — and the table's certificate proves the explorer checked
    it anyway.

    Without a table applied, every override falls back to the plain
    SloPolicy behavior (``make_policy("table")`` must construct without
    arguments; the engine applies the artifact right after, enforced by
    GC011 at load time)."""

    name = "table"

    def __init__(self) -> None:
        super().__init__()
        self.table: Optional[dict] = None
        self._vec = None
        self._step_no = 0

    def reset(self) -> None:
        super().reset()
        self._step_no = 0

    def apply(self, table: Mapping) -> None:
        """Install a (parsed) policy-table artifact. Callers wanting the
        GC011 checks go through :meth:`SloPolicy.from_table` or the
        engine's loader — ``apply`` itself trusts its input so the
        certification harness can run a not-yet-stamped candidate."""
        from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
            PolicyVector,
        )

        self.table = dict(table)
        self._vec = PolicyVector.from_dict(self.table.get("vector", {}))
        slo = self.table.get("slo", {})
        self.tenant_weights = dict(slo.get("tenant_weights", {}))
        self.burn_threshold = float(slo.get("burn_threshold", 1.0))

    @property
    def table_id(self) -> str:
        return str(self.table.get("table_id", "")) if self.table else ""

    def _rank(self, cls: str, burning: frozenset):
        if self._vec is None:
            return super()._rank(cls, burning)
        return self._vec.rank(cls, cls in burning)

    def _prefill_budget(self, view: EngineView) -> Optional[int]:
        if self._vec is None:
            return super()._prefill_budget(view)
        ttft_burn, tpot_burn = view.slo_burn
        if ttft_burn >= self.burn_threshold:
            state = "ttft_burn"
        elif tpot_burn >= self.burn_threshold:
            state = "tpot_burn"
        else:
            state = "calm"
        return self._vec.budget_for(state)

    def actions(self, view: EngineView) -> Iterator[StepAction]:
        if self._vec is None:
            yield from super().actions(view)
            return
        # the SloPolicy/Fifo arm structure with the table's two choice
        # points: a VERIFY arm only every `verify_cadence` steps, and
        # the async lookahead only when the table prefers it
        self._step_no += 1
        cfg = view.config
        spec_on = view.spec_enabled and view.degrade_level < 1
        async_on = view.degrade_level < 2
        cadence = max(int(self._vec.verify_cadence), 1)
        if (
            spec_on
            and self._spec_pause <= 0
            and self._step_no % cadence == 0
        ):
            yield StepAction(ActionType.READBACK)
            yield StepAction(ActionType.ADMIT, meta=self._admit_meta(view))
            yield StepAction(
                ActionType.PREFILL_CHUNK, meta=self._prefill_meta(view)
            )
            yield StepAction(ActionType.VERIFY)
            if not view.last_verify_drafted:
                if async_on:
                    self._spec_pause = cfg.spec_retry_steps
                yield StepAction(ActionType.DECODE_DISPATCH, mode="sync")
            return
        if self._spec_pause > 0:
            self._spec_pause -= 1
        if async_on and self._vec.prefer_async and view.async_eligible:
            yield StepAction(ActionType.DECODE_DISPATCH, mode="async")
            if not view.last_async_fell_back:
                return
        yield StepAction(ActionType.READBACK)
        yield StepAction(ActionType.ADMIT, meta=self._admit_meta(view))
        yield StepAction(
            ActionType.PREFILL_CHUNK, meta=self._prefill_meta(view)
        )
        yield StepAction(ActionType.DECODE_DISPATCH, mode="sync")
