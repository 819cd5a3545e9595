"""Paged serving engine: block-budget admission, prefix-cached prefill,
preempt-and-requeue under pool pressure.

:class:`..inference.engine.ContinuousBatchingEngine` schedules *slots*:
every admitted request owns a dense ``max_seq_len`` KV row, so capacity is
fixed at ``max_batch`` regardless of how short requests actually are, and
identical prompt prefixes are re-prefilled from scratch. This engine keeps
the slot scheduler's decode shape (one batched T=1 program advancing every
active lane) but replaces the memory model underneath:

- KV rows live in a global pool of fixed-size blocks
  (:class:`..inference.model.PagedKVCache`); each request carries a block
  table and the jitted programs translate logical rows through it
  (vLLM PagedAttention).
- A :class:`.radix_index.RadixPrefixIndex` maps token prefixes to block
  chains: a new request's shared prefix is admitted *by reference*
  (reported as ``cached_tokens``) and only the suffix is prefilled
  (SGLang RadixAttention).
- Admission is block-budget control: admit while free + evictable blocks
  cover the prompt plus a decode reserve. On pool exhaustion mid-decode the
  youngest request is preempted and requeued (its registered prefix blocks
  park in the cached LRU, so resumption usually re-admits by reference) —
  never an exception out of :meth:`step`.
- With ``PagedConfig.prefill_chunk_tokens`` set, a long uncached suffix is
  prefilled in fixed-token chunks, one per :meth:`step`, interleaved with
  the decode batch for already-active lanes (Sarathi-Serve chunked
  prefill) — only the final chunk samples the request's first token.

Greedy outputs are token-identical to the dense engine: the paged gather
feeds the same K/V values in the same logical order to the same
``_cache_attention``, and masked garbage rows contribute exactly zero.
Stochastic sampling is supported but consumes a different rng-split order
than the dense engine, so sampled streams are valid, not bit-matching.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_llama3_2_tpu.inference.engine import (
    GenerationConfig,
    InferenceEngine,
    read_host_tokens,
)
from neuronx_distributed_llama3_2_tpu.serving.catalog import (
    CatalogManifest,
    complete_ladder,
    pick_bucket,
    validate_ladder,
)
from neuronx_distributed_llama3_2_tpu.serving.faults import (
    EngineStalledError,
    FaultInjector,
    InjectedFault,
)
from neuronx_distributed_llama3_2_tpu.inference.model import (
    cache_block_bytes,
    cache_row_bytes,
)
from neuronx_distributed_llama3_2_tpu.inference.placement import (
    committed_home,
)
from neuronx_distributed_llama3_2_tpu.inference.sampling import (
    GREEDY_TEMPERATURE,
    SamplingConfig,
    sample,
    sample_lanes,
)
from neuronx_distributed_llama3_2_tpu.moe import tap as routing_tap
from neuronx_distributed_llama3_2_tpu.serving.block_allocator import (
    NULL_BLOCK,
    BlockAllocator,
    HostTier,
)
from neuronx_distributed_llama3_2_tpu.serving.metrics import ServingMetrics
from neuronx_distributed_llama3_2_tpu.serving.policy import (
    ActionType,
    EngineView,
    POLICY_ACTIONS,
    StepAction,
    StepPolicy,
    make_policy,
)
from neuronx_distributed_llama3_2_tpu.serving.slo import SLOMonitor, SLOPolicy
from neuronx_distributed_llama3_2_tpu.serving.radix_index import (
    SPILLED_BLOCK,
    RadixPrefixIndex,
)
from neuronx_distributed_llama3_2_tpu.serving.tracing import (
    EngineTracer,
    program_label,
)
from neuronx_distributed_llama3_2_tpu.utils.logger import get_logger
from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP

logger = get_logger()


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _in_scope(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)``: every HLO instruction
    of the program carries ``name`` as the first segment of its ``op_name``
    path, which is how a device trace tells the serving programs apart
    (serving/tracing.py ``PROGRAM_SCOPES``). ``functools.wraps`` keeps the
    callable's ``__name__``, so the XLA module is called what it was."""

    @functools.wraps(fn)
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)

    return scoped


# The programs a traced engine taps for routing counters (moe/tap.py), and
# which (lane, row) slots of a call carry a request's token: a prefill bucket's
# rows up to the chunk's length, a decode step's lanes that hold a table. The
# rest is padding, which routes like any row and is not counted.
_LIVE_ROWS = {
    "pctx": lambda params, cache, ids, length, *_: (
        jnp.arange(ids.shape[1]) < length[:, None]),
    "psfx": lambda params, cache, ids, start, length, *_: (
        jnp.arange(ids.shape[1]) < length[:, None]),
    "pdecode": lambda params, cache, tokens, positions, tables, *_: (
        tables[:, :1] != NULL_BLOCK),
}


def _with_routing_tap(fn, record: "ProgramRecord"):
    """``fn`` traced with a routing tap open: returns ``(fn's outputs, live
    tokens routed to each expert over all layers)`` — ``None`` for a model
    without experts — and leaves on ``record.routing`` what the trace showed
    of the expert block: the dispatch paths taken, the (token, expert)
    pairs they compute per call, and which of the router's experts the
    model holds."""
    live_rows = _LIVE_ROWS[record.kind]

    @functools.wraps(fn)
    def tapped(*args):
        with routing_tap.open_tap(live_rows(*args)) as tap:
            out = fn(*args)
        record.routing = {
            "paths": tuple(sorted(tap.paths)),
            "pairs_computed": tap.pairs_computed,
            "held": tap.held,
        }
        return out, tap.tokens_per_expert

    return tapped


def _aval_of(x):
    """ShapeDtypeStruct twin of an array leaf (non-arrays pass through) —
    what a :class:`ProgramRecord` remembers about its first dispatch so
    the auditor can re-lower/retrace without holding live buffers."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        if getattr(x, "committed", False):
            # a committed argument keeps its placement, sharding and layout
            # (a weight may rest in a layout of its own, inference/
            # placement.py): re-lowering is then the dispatch's own lowering,
            # a cache hit, and not a second program
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.format)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


@dataclasses.dataclass
class ProgramRecord:
    """One compiled serving program plus the metadata graftcheck audits.

    Every jitted program the engine dispatches lives in the ``_programs``
    registry as one of these (``_register_program`` is the single
    ``jax.jit`` site on the serving path — shardlint SL007 enforces
    that). The record keeps the *raw* python callable and, after the
    first dispatch, the example avals, so ``analysis.graftcheck`` can
    retrace the jaxpr (GC001/GC003/GC004/GC005) and re-lower for the
    donation-aliasing check (GC002) without touching live state.
    """

    key: tuple
    kind: str                     # "pctx" | "psfx" | "pdecode" | ...
    fn: Any                       # raw callable (pre-jit)
    donate_argnums: tuple = ()
    gather: bool = False          # kernel-shed (dense-gather) variant
    checked: bool = False         # finite-verified variant
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    jitted: Any = None
    example_args: Optional[tuple] = None  # avals of the first dispatch
    # traced engines only (``_with_routing_tap``): the program also returns
    # its per-expert token counts, handed to ``on_routed`` at every dispatch
    # as the device array they are — nothing waits for them
    routing: Optional[Dict[str, Any]] = None
    on_routed: Any = None

    def __call__(self, *args):
        if self.example_args is None:
            self.example_args = tuple(
                jax.tree.map(_aval_of, a) for a in args
            )
        if self.on_routed is None:
            return self.jitted(*args)
        out, counts = self.jitted(*args)
        if counts is not None:
            self.on_routed(self, counts)
        return out

    def lower(self):
        """Re-lower at the recorded example avals (trace-cache hit — the
        program was already compiled at these avals)."""
        if self.example_args is None:
            raise ValueError(f"program {self.key!r} was never dispatched")
        return self.jitted.lower(*self.example_args)


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Knobs for the paged KV pool (see docs/serving.md)."""

    block_size: int = 16
    # pool size INCLUDING the reserved null block (id 0): usable capacity is
    # (num_blocks - 1) * block_size token rows shared by all requests
    num_blocks: int = 128
    # admission headroom: blocks a request must be able to claim beyond its
    # prompt before it is admitted, delaying the first preemption
    decode_reserve_blocks: int = 2
    enable_prefix_caching: bool = True
    # -- tiered KV storage (docs/serving.md "Tiered KV storage") --
    # spill eviction victims' payloads into a host-RAM tier behind the
    # radix index instead of discarding them: the trie node survives in a
    # `spilled` residency state and a later prefix hit restores the blocks
    # H2D (metered, never on the steady-state path) when the cost model
    # says the transfer beats re-prefilling. Requires
    # enable_prefix_caching and a positive host_tier_bytes.
    spill_enabled: bool = False
    # byte budget of the host tier; its own LRU evicts past it (dropping
    # the spilled trie nodes whose payloads are gone)
    host_tier_bytes: int = 0
    # restore-vs-recompute crossover: restore a spilled run when
    # restore_seconds <= restore_crossover * recompute_seconds, priced from
    # graftmeter CostProfiles (payload bytes over a PCIe-class host link vs
    # prefill FLOPs at the padded rung). 1.0 = break-even; large values
    # force restoring (tiny-model test harnesses, where prefill is nearly
    # free); 0 declines every restore while still spilling.
    restore_crossover: float = 1.0
    # bound on enqueued-but-undrained D2H spill snapshots; the oldest
    # entries drain early (blocking) when the queue tops out
    spill_queue_depth: int = 8
    cache_dtype: Any = None
    # quantized KV pool (docs/serving.md "Quantized KV pool"): store the
    # block pool int8/fp8 with per-(row, kv-head) absmax scales and dequant
    # on read (in-kernel after the block DMA on the Pallas path, outside the
    # kernel on the gather fallbacks) — ~2x resident lanes or kv_limit per
    # chip at fixed pool bytes. "bf16" = fp passthrough: pool at the model
    # (or cache_dtype) precision, no scale arrays, trace unchanged.
    kv_cache_dtype: str = "bf16"
    # low-precision MXU decode dot (docs/serving.md "On-device sampling &
    # the low-precision MXU dot"): keep the quantized pool's int8/fp8
    # payload as a q·k dot operand in the Pallas decode kernel (int8×int8
    # accumulating int32 / fp8 with preferred_element_type=f32) and apply
    # the absmax scales to the fp32 score outputs, instead of
    # dequant-widening every block to fp32 before the dot. Requires a
    # quantized kv_cache_dtype; graftcheck GC005 is knob-aware (the
    # fp32-widening requirement applies iff this is off).
    quant_mxu: bool = False
    # fused on-device sampling (docs/serving.md "On-device sampling"):
    # compile temperature/top-k/top-p + categorical INTO the decode /
    # verify / prefill programs, with per-lane (temperature, top_k, top_p)
    # params and per-lane PRNG key data as device-resident arrays mutated
    # only through the lane_set scatter — sampled traffic keeps the
    # steady-state h2d_uploads == 0 property greedy traffic has, and the
    # greedy-only speculative guard lifts (verify's accept targets become
    # position-keyed draws). Greedy configs ride the same program via the
    # temperature <= 0 sentinel, token-identically to the host-key path.
    on_device_sampling: bool = False
    metrics_log_every: int = 0  # decode steps between metric log lines; 0=off
    # chunked prefill (Sarathi-Serve): split an admission whose uncached
    # suffix exceeds this many tokens into fixed-budget chunks, one per
    # step(), interleaved with decode batches for the already-active lanes —
    # a long prompt no longer stalls every decode stream for its whole
    # prefill. None/0 = off (whole-suffix prefill at admission, as before).
    prefill_chunk_tokens: Optional[int] = None
    # fused mixed-mode step (docs/serving.md "Fused mixed-mode step"): pack
    # decode lanes, speculative-verify rows and this step's active
    # prefill-chunk suffixes into ONE multi-row program (`pmixed`) over the
    # shared paged pool, dispatched once per step — the separate
    # per-prefilling-lane psfx dispatch loop disappears and the catalog
    # sheds the psfx bucket×kv product for a single mixed t rung. Token-
    # identical to the unfused engine; pure-decode steady state still runs
    # the plain pdecode/pverify programs (zero-upload, GC003). Host
    # sampling must be greedy (on_device_sampling lifts that, exactly as
    # it does for speculation).
    fused_step: bool = False
    # speculative decoding (docs/serving.md "Speculative decoding"): draft
    # up to this many tokens per lane per step and verify them in ONE
    # multi-token forward — accepted drafts multiply tokens/step. 0 = off.
    # Greedy host sampling compares the target's argmax; with
    # on_device_sampling the verify targets are the same position-keyed
    # draws sequential decoding would make, so sampled lanes speculate too.
    spec_draft_tokens: int = 0
    # tree speculation (docs/serving.md "Tree speculation"): drafts become
    # a packed candidate TREE of up to spec_draft_tokens nodes — several
    # branches share one ancestor-masked verify forward (`ptree`) and the
    # deepest accepted root path commits, so drafty-but-ambiguous traffic
    # beats a single chain at the same draft budget. Requires
    # spec_draft_tokens > 0; drafters without propose_tree degrade to
    # single-chain trees (token-identical to linear speculation).
    spec_tree: bool = False
    # branch fan-out the default prompt-lookup drafter targets per tree
    spec_tree_branches: int = 2
    # n-gram window of the default prompt-lookup drafter (serving/drafter.py)
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    # draft-disable heuristic: once a request has been offered at least
    # spec_probation_tokens drafts, it drops to plain decode for good when
    # its personal accept rate sits below spec_min_accept_rate (counted in
    # ServingMetrics.spec_disabled_lanes) — a lane the drafter keeps
    # guessing wrong on should not pay the verify-width forward
    spec_min_accept_rate: float = 0.2
    spec_probation_tokens: int = 32
    # verify steps need same-step readback (the accept length decides how
    # far each lane advanced), so a drafting step is a drained one; when
    # the drafter abstains for every lane the look-ahead runs instead and
    # drafting is re-tried after this many steps. 0 = re-try every step
    # (the look-ahead then runs only while every active request is
    # spec-disabled).
    spec_retry_steps: int = 4
    # -- fault tolerance (docs/serving.md "Failure handling & degradation") --
    # on-device finite-logit check: decode/verify programs grow a (B,) bool
    # `finite` output and a lane whose logits go NaN/Inf is quarantined
    # (terminal `failed`, blocks released) instead of committing garbage
    # tokens. Off by default: the unchecked traces stay bitwise unchanged.
    # A FaultInjector with nan faults turns this on implicitly.
    detect_nonfinite: bool = False
    # run the invariant auditor (serving/invariants.py) every N steps;
    # violations are logged + counted in ServingMetrics.audit_violations.
    # 0 = off (default — no audit cost on the serving path).
    audit_interval: int = 0
    # debug mode: audit strictly (raise InvariantViolation) at every
    # finish / preempt / fail transition — for tests and soak teardowns
    audit_debug: bool = False
    # stall watchdog: consecutive step()s with work outstanding but zero
    # progress (no tokens, no admissions, no finishes, no preemptions, no
    # prefill movement) before step() raises EngineStalledError naming the
    # stuck lanes. 0 = off (seed-compatible default; production fronts
    # should set it so run_to_completion can never spin forever).
    stall_step_limit: int = 0
    # degradation ladder: after this many fault/pressure events inside a
    # degrade_window_steps window, shed one feature rung (spec -> async
    # lookahead -> paged kernel -> preempt-shed); each rung steps back up
    # after degrade_recover_steps clean steps. 0 = ladder off (default).
    degrade_after_faults: int = 0
    degrade_window_steps: int = 64
    degrade_recover_steps: int = 64
    # -- observability (docs/serving.md "Observability") --
    # graftscope flight recorder: record one structured event per engine
    # phase (admit wave, prefill chunk, decode/verify dispatch tagged with
    # its ProgramRecord key, readback, lane/table flushes, fault and
    # ladder instants) into a per-step ring buffer, exportable as Chrome
    # trace-event JSON via engine.export_trace(path). Pure host-side
    # python around the existing funnels: no uploads, no syncs, no new
    # program keys (graftcheck GC003/GC006 — and the GC007/GC008 catalog
    # contract — hold with tracing on). Request timestamps and the
    # latency histograms are metrics, not tracing — they stay on
    # regardless of this flag.
    trace_enabled: bool = False
    # ring-buffer capacity of the flight recorder: only the last N steps
    # are retained, so trace memory is bounded however long the engine runs
    trace_buffer_steps: int = 256
    # -- compiled-program catalog (docs/serving.md "Compiled-program
    #    catalog"; serving/catalog.py) --
    # override the serving bucket ladders dispatch shapes pad into.
    # kv_buckets: the kv_limit attention extents of decode/verify/suffix
    # programs; prefill_buckets: the padded prompt/chunk token counts of
    # pctx/psfx programs. None = the InferenceEngine's bucket ladder.
    # Either ladder gets max_seq_len appended when it tops out early (a
    # dispatch past the ladder must still route somewhere).
    kv_buckets: Optional[tuple] = None
    prefill_buckets: Optional[tuple] = None
    # compile the ENTIRE declared CatalogManifest at engine start through
    # _register_program, then freeze the registry (mark_steady): no
    # request ever pays a compile in its TTFT, and graftcheck GC007/GC008
    # turn any out-of-catalog or post-freeze compile into a finding.
    # False: each program registers and compiles at its first dispatch.
    prewarm: bool = False
    # -- graftmeter: device-cost ledger + SLO burn-rate alerts
    #    (docs/serving.md "Cost accounting & SLOs"; serving/accounting.py,
    #    serving/slo.py) --
    # harvest per-program CostProfiles + the HBM ledger at the end of
    # prewarm() (static, host-only; never touches the dispatch path)
    cost_accounting: bool = True
    # override the per-chip HBM budget the ledger headrooms against
    # (None = device memory_stats()["bytes_limit"], else a 16 GiB default)
    hbm_budget_bytes: Optional[int] = None
    # latency objectives: p99 targets in milliseconds; None = objective
    # not declared. With neither set, the SLO monitor is never built.
    slo_ttft_p99_ms: Optional[float] = None
    slo_tpot_p99_ms: Optional[float] = None
    slo_eval_steps: int = 16       # engine steps between burn evaluations
    slo_burn_window: int = 4       # evaluations per rolling burn window
    slo_burn_threshold: float = 1.0  # windowed burn that raises an alert
    # sustained burn feeds the PR 8 degradation ladder through the same
    # _note_event funnel chaos faults use (ladder knobs must also be on)
    slo_degrade: bool = False
    # -- step scheduling (docs/serving.md "Step policy"; serving/policy.py) --
    # name of the registered StepPolicy choosing each step's action
    # schedule. "fifo" is the historical inlined phase order,
    # byte-for-byte. A policy *instance* can also be passed to the engine
    # constructor (policy=), e.g. for the graftsched explorer's permuted
    # schedules; the config knob stays a name so PagedConfig remains
    # hashable/frozen.
    step_policy: str = "fifo"
    # path to a graftplan certified policy-table artifact
    # (analysis/graftplan.py). Loaded at construction under GC011 —
    # certificate present, automaton/ladder fingerprints fresh against
    # *this* engine — and applied to the policy (which must be
    # TablePolicy, i.e. step_policy="table"). None = no table.
    policy_table_path: Optional[str] = None


#: graftserve service classes a request may be submitted under. The class
#: is a scheduling hint for SLO-aware policies (serving/scheduler.py) and
#: a metrics label; it never reaches the device path.
SERVICE_CLASSES = frozenset({"interactive", "batch"})


@dataclasses.dataclass
class _PagedRequest:
    rid: int
    prompt: List[int]
    out: List[int]
    lane: Optional[int] = None
    table: List[int] = dataclasses.field(default_factory=list)
    position: int = 0            # == len(prompt + out) - 1 while active
    cached_tokens: int = 0       # cumulative across (re-)admissions
    preemptions: int = 0
    done: bool = False
    # chunked prefill: admitted (lane + blocks held) but still materializing
    # the prompt one chunk per step; joins the decode batch only when
    # prefill_pos reaches prefill_target (= len(prompt + out) at admission)
    prefilling: bool = False
    prefill_pos: int = 0
    prefill_target: int = 0
    # chunked prefill: the (1, W) device block table shared by every chunk
    # of this admission (the table is fixed for the whole chunk walk, so it
    # uploads once, not once per chunk); dropped on install/preempt/finish
    table_dev: Any = None
    # speculative decoding: per-request acceptance telemetry driving the
    # draft-disable heuristic (PagedConfig.spec_min_accept_rate)
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_disabled: bool = False
    # terminal failure (fault injection, non-finite logits, device error):
    # the request is done with partial output and `error` holds the detail
    failed: bool = False
    error: Optional[str] = None
    # lifecycle timestamps (time.perf_counter seconds, always recorded):
    # request_info derives queue_ms/ttft_ms/tpot_ms from these, and they
    # survive into the terminal record (finished AND failed requests keep
    # their timing context)
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None    # first admission only
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    prefill_ms: float = 0.0                # cumulative across re-admissions
    # graftserve admission metadata: the service class routes the request
    # into a latency tier (interactive = TTFT-sensitive, batch =
    # throughput) and the tenant is the fairness principal an SLO-aware
    # policy stripes admission across. Pure scheduling hints — the FIFO
    # policy and the device path never read them.
    service_class: str = "batch"
    tenant: str = "default"
    # engine _step_index at submit() time: the workload-trace export
    # (graftplan) replays arrivals at the same step boundary
    submitted_step: int = 0


def _in_setup_record(init):
    """``PagedServingEngine.__init__`` as the ``setup.paged_engine`` span of
    the process's set-up record (utils/setup_record.py), and — once the span has
    closed — the phase table at INFO: why this replica took as long as it did
    to come up."""

    @functools.wraps(init)
    def construct(self, *args, **kwargs):
        with SETUP.span("setup.paged_engine"):
            init(self, *args, **kwargs)
        logger.info(
            "set-up so far, seconds by phase: %s",
            {k: round(v, 3) for k, v in SETUP.summary().items()},
        )

    return construct


class PagedServingEngine:
    """Block-granular continuous batching over an :class:`InferenceEngine`'s
    model/params. The dense engine's cache and programs are untouched — the
    paged path is opt-in (construct this class, or
    :func:`make_serving_engine` with a :class:`PagedConfig`)."""

    @_in_setup_record
    def __init__(
        self,
        engine: InferenceEngine,
        gen: GenerationConfig = GenerationConfig(),
        paged: PagedConfig = PagedConfig(),
        drafter: Optional[Any] = None,
        injector: Optional[FaultInjector] = None,
        policy: Optional[StepPolicy] = None,
    ) -> None:
        self.engine = engine
        self.model = engine.model
        self.gen = gen
        self.paged = paged
        # chaos harness (serving/faults.py): None in production — every
        # injector branch below is `is not None`-guarded so the fault-free
        # path stays bitwise identical to an engine built without one
        self.injector = injector
        bs = paged.block_size
        if bs < 1:
            raise ValueError("block_size must be positive")
        if paged.decode_reserve_blocks < 1:
            # a solo request's re-admission after self-preemption is only
            # guaranteed to fit when admission kept >= 1 block of headroom
            raise ValueError("decode_reserve_blocks must be >= 1")
        self._spec_k = int(paged.spec_draft_tokens or 0)
        if self._spec_k < 0:
            raise ValueError("spec_draft_tokens must be >= 0")
        # what the engine learns from the decode model about its cache: rows
        # by position (a prefix of the rows is a prefix's cache, a padded or
        # rejected row is masked or overwritten), or a state that is neither
        # (docs/serving.md "Models whose cache is a state")
        self._positional = bool(self.model.cache_is_positional)
        # 1 where every pdecode holds the one-pass state kernel, else 0
        self._state_kernel = int(self.model.uses_state_kernel())
        # some layers read a block of rows tile by tile under a block mask
        self._chunk_tiles = self.model.chunk_read() is not None
        # a kind of cache that is laid out a lane, beside the allocator's pool
        # (docs/serving.md "Stacks whose layers cache different things"): the
        # last rows of a context in a ring of blocks (window layers beside full
        # ones), or a state in one slot (state-space layers beside attention).
        # This engine sizes it and lays it out
        self._lane_kind = next(
            (kind for kind in self.model.cache_kinds if kind.rows is not None), None
        )
        # some layer keeps a state: the whole cache (one a block, from the
        # allocator), or the kind laid out a lane
        state_kind = self._lane_kind is not None and self._lane_kind.state
        self._has_state = bool(self.model.keeps_state)
        if self._has_state:
            what = (
                f"its {self._lane_kind.name} layers keep a state a lane"
                if state_kind else "its cache is a state per sequence"
            )
            for on, name, why in (
                (self._spec_k, "spec_draft_tokens > 0",
                 "a rejected draft cannot be taken back out of a state"),
                (paged.fused_step, "fused_step",
                 "the mixed program writes every lane's padding rows, and a "
                 "state keeps what a row did to it"),
                (paged.spill_enabled, "spill_enabled",
                 "the spill tier lives behind the prefix index, which shares "
                 "nothing of a state"),
            ):
                if on:
                    raise ValueError(
                        f"{name} is not available for {type(self.model).__name__}: "
                        f"{what}, not rows per token — {why}"
                    )
        elif self._lane_kind is not None:
            for on, name, why in (
                (self._spec_k, "spec_draft_tokens > 0",
                 "the ring is sized for a prefill rung of fresh rows; a verify "
                 "block's rejected rows would have to be kept out of it as well"),
                (paged.fused_step, "fused_step",
                 "the mixed program has no table for the ring"),
                (paged.spill_enabled, "spill_enabled",
                 "the spill tier lives behind the prefix index, and a prefix "
                 "of the full layers' blocks is not a prefix's cache"),
            ):
                if on:
                    raise ValueError(
                        f"{name} is not available for {type(self.model).__name__}: its "
                        f"{self._lane_kind.name} layers keep a ring of rows a lane — {why}"
                    )
        # prefix sharing matches token by token inside a block and copies a
        # partly shared block; a state after N tokens says nothing about its
        # first k, so a model with a state neither matches nor inserts. Nor
        # does a stack with a ring: a hit at token N would leave the ring's
        # layers without the rows N - window + 1 .. N - 1
        self._share_prefixes = (
            bool(paged.enable_prefix_caching) and self._positional
            and self._lane_kind is None
        )
        # tree speculation: verify a packed candidate tree (ptree program)
        # instead of a single chain. Set before the catalog build below —
        # the manifest swaps its verify rungs to ptree keys under the flag.
        self._spec_tree = bool(paged.spec_tree)
        if self._spec_tree and not self._spec_k:
            raise ValueError(
                "spec_tree requires spec_draft_tokens > 0 (the tree's node "
                "budget IS the draft-token budget)"
            )
        if self._spec_tree and self._spec_k + 1 > 32:
            raise ValueError(
                "spec_tree packs ancestor sets into int32 bitmasks — "
                f"spec_draft_tokens ({self._spec_k}) must be <= 31"
            )
        if paged.spec_tree_branches < 1:
            raise ValueError("spec_tree_branches must be >= 1")
        # fused on-device sampling (docs/serving.md "On-device sampling"):
        # per-lane params + PRNG key data live device-resident and the
        # decode/verify/prefill programs sample in-fuse
        self._fused = bool(paged.on_device_sampling)
        if self._spec_k and not gen.sampling.greedy and not self._fused:
            # host-sampled acceptance compares the target's argmax; a
            # sampled stream would silently stop matching the plain loop.
            # Fused sampling lifts this: verify's accept targets become
            # position-keyed draws (LlamaDecode.verify_step sampling=).
            raise ValueError(
                "speculative serving with host sampling requires greedy "
                "(SamplingConfig(greedy=True)) — or turn on "
                "PagedConfig.on_device_sampling for sampled verify"
            )
        # fused mixed-mode step (docs/serving.md "Fused mixed-mode step"):
        # one pmixed dispatch serves decode + verify + prefill-chunk rows
        # whenever any lane is mid-prefill; the mixed row width t covers
        # the chunk budget and the widest verify block
        self._fused_step = bool(paged.fused_step)
        if self._fused_step and not gen.sampling.greedy and not self._fused:
            # the mixed program draws every row's token in one dispatch —
            # a host-keyed sampled stream cannot replay the unfused
            # engine's per-program key-split order. Fused sampling keys
            # draws by landing index, which is dispatch-shape-independent.
            raise ValueError(
                "fused_step with host sampling requires greedy "
                "(SamplingConfig(greedy=True)) — or turn on "
                "PagedConfig.on_device_sampling for sampled mixed steps"
            )
        self._mixed_t = (
            max(int(paged.prefill_chunk_tokens or 8), self._spec_k + 1)
            if self._fused_step else 0
        )
        self.drafter = drafter
        if self._spec_k and self.drafter is None:
            from neuronx_distributed_llama3_2_tpu.serving.drafter import (
                NGramDrafter,
            )

            self.drafter = NGramDrafter(
                max_n=paged.spec_ngram_max, min_n=paged.spec_ngram_min
            )
        # step scheduling policy (serving/policy.py): each step() asks it
        # for the action schedule; the drafting-pause counter that used to
        # live here is FifoPolicy state now (it IS a scheduling decision)
        self.policy = policy if policy is not None else make_policy(
            paged.step_policy
        )
        self.policy.reset()
        self._view = EngineView(self)
        # outcome flags the policy generator reads after an action executes
        self._last_verify_drafted = False
        self._last_async_fell_back = False
        self._last_mixed_dispatched = False
        # why this step's decode was not dispatched ahead (_note_declined)
        self._declined: Optional[str] = None
        # graftsched action trace: per-step (step_index, pending_at_start,
        # [StepAction...]) records, ring-bounded like the flight recorder;
        # analysis/graftsched.py replays it against the legality automaton
        # (GC010). _on_action is the explorer's per-transition audit hook.
        self.action_trace: deque = deque(
            maxlen=paged.trace_buffer_steps or 256
        )
        self._step_actions: List[StepAction] = []  # pre-step emissions: untraced
        self._on_action = None
        # declared bucket ladders (serving/catalog.py): every dispatch
        # shape pads into one of these rungs, so the compiled-program set
        # is O(ladder) however heterogeneous traffic gets. Each ladder
        # must route the longest dispatch even when the declared rungs top
        # out early (dense decode has the same clamp fallback): a kv
        # extent reaches max_seq_len; a prefill reaches max_seq_len only
        # on an engine that does not chunk — with a chunk set, an
        # admission whose uncached suffix is longer is chunked and every
        # other one is at most a chunk (_admit, _advance_prefills), so the
        # ladder ends at the chunk's rung (the fused step's row width
        # where that is larger) and no whole-prompt program is compiled.
        chunk = paged.prefill_chunk_tokens
        self._prefill_buckets = complete_ladder(
            paged.prefill_buckets or engine.buckets, engine.max_seq_len,
            max(int(chunk), self._mixed_t) if chunk else None,
        )
        self._kv_buckets = complete_ladder(
            paged.kv_buckets or engine.buckets, engine.max_seq_len
        )
        # table width: logical blocks covering max_seq_len, plus overflow
        # entries (always null) absorbing writes past it — a padded prefill
        # that starts near max_seq_len reaches one prefill rung further, a
        # verify at the sequence cap its rejected tail of spec_k rows.
        # Nothing reads the overflow entries; they follow the top rung
        self.table_width = _ceil_div(engine.max_seq_len, bs) + _ceil_div(
            max(self._prefill_buckets[-1], self._spec_k), bs
        )
        from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (
            kv_cache_jax_dtype,
        )

        kv_cache_jax_dtype(paged.kv_cache_dtype)  # validate the knob early
        self._kv_quantized = paged.kv_cache_dtype != "bf16"
        if self._kv_quantized and paged.cache_dtype is not None:
            raise ValueError(
                "cache_dtype and a quantized kv_cache_dtype are mutually "
                "exclusive — the quantized storage dtype IS the pool dtype"
            )
        if paged.quant_mxu:
            if not self._kv_quantized:
                raise ValueError(
                    "quant_mxu requires a quantized kv_cache_dtype "
                    "(int8/fp8) — the fp pool has no low-bit payload to "
                    "keep on the MXU"
                )
            if not getattr(self.model.config, "quant_mxu", False):
                # config twin carrying the kernel knob (same weightless
                # pattern as the kernel-shed gather twin): every program
                # traced below binds the low-precision-dot model, so the
                # engine IS the knob's scope — the caller's model object
                # is untouched
                self.model = type(self.model)(
                    dataclasses.replace(self.model.config, quant_mxu=True)
                )
        # what a lane holds of the kind laid out a lane. A ring: every row a
        # query of the top prefill rung still sees (rows - 1 behind its first
        # row) plus the rung's own fresh rows, padding included, in whole
        # blocks. A state: one slot. Lane l's is blocks 1 + l * n .. of the
        # kind's pool, block 0 its null block. Laid out once: no allocator,
        # no release, no per-step delta — a request that is preempted or
        # re-admitted finds nothing of its own there and builds it by prefill
        self._lane_blocks = 0
        self._lane_tables: Optional[np.ndarray] = None
        sized = {}
        if self._lane_kind is not None:
            self._lane_blocks = 1 if self._lane_kind.state else _ceil_div(
                self._lane_kind.rows - 1 + self._prefill_buckets[-1], bs
            )
            self._lane_tables = 1 + np.arange(
                engine.max_batch * self._lane_blocks, dtype=np.int32
            ).reshape(engine.max_batch, self._lane_blocks)
            sized = {
                f"{self._lane_kind.name}_blocks": 1 + engine.max_batch * self._lane_blocks
            }

        def init_pool():
            return self.model.init_paged_cache(
                paged.num_blocks, bs, paged.cache_dtype,
                kv_cache_dtype=paged.kv_cache_dtype, **sized,
            )

        from neuronx_distributed_llama3_2_tpu.parallel import (
            state as parallel_state,
        )

        # mesh-replicated committed sharding for the device-resident state:
        # programs return their outputs committed to NamedSharding(mesh, P()),
        # so constructing the residents on the SAME sharding keeps every
        # dispatch on one lowering (uncommitted single-device inputs would
        # re-lower each program on its second call — graftcheck GC008)
        self._resident_sharding = None
        if parallel_state.model_parallel_is_initialized():
            # the pool is born sharded: each device zero-fills only its own
            # kv-head slice, so a tp mesh can hold a pool tp× one chip's —
            # building it whole on device 0 first would cap it at one HBM
            mesh = parallel_state.get_parallel_state().mesh
            shardings = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                self.model.paged_cache_specs(quantized=self._kv_quantized),
            )
            self.cache = jax.jit(init_pool, out_shardings=shardings)()
            if mesh.size > 1:
                self._resident_sharding = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()
                )
        elif (home := committed_home(engine.params)) is not None:
            # committed weights on one device (a re-placed leaf is): the
            # pool and the residents are born committed beside them, for
            # the same reason as on a mesh
            self.cache = jax.jit(init_pool, out_shardings=home)()
            self._resident_sharding = home
        else:
            self.cache = init_pool()
        self.allocator = BlockAllocator(paged.num_blocks, bs)
        self.index = RadixPrefixIndex(self.allocator)
        # tiered KV storage (docs/serving.md "Tiered KV storage"): the
        # host-RAM spill tier behind the radix index. _spill MUST be set
        # before the catalog is built below — spill adds the
        # block_save/block_restore move keys to the legal key universe
        # (graftcheck GC007).
        self._spill = bool(paged.spill_enabled)
        self.host_tier: Optional[HostTier] = None
        # enqueued-but-undrained D2H snapshots: (sid, device arrays, nbytes)
        self._spill_pending: deque = deque()
        self._restore_dims = None  # cached EngineDims for restore pricing
        if self._spill:
            if not paged.enable_prefix_caching:
                raise ValueError(
                    "spill_enabled requires enable_prefix_caching (the "
                    "spilled residency state lives in the radix index)"
                )
            if paged.host_tier_bytes <= 0:
                raise ValueError(
                    "spill_enabled requires a positive host_tier_bytes"
                )
            self.host_tier = HostTier(
                paged.host_tier_bytes,
                on_evict=self.index.invalidate_spilled,
            )
            self.allocator.host_tier = self.host_tier
            self.allocator.spill_hook = self._spill_block
            self.index.on_spill_drop = self._drop_spill_payload
        self.metrics = ServingMetrics()
        # a snapshot taken with no arguments (the front door's, the
        # benchmark's) carries the pool's and the radix index's counts too
        self.metrics.bind(self.allocator, self.index)
        # graftscope flight recorder (serving/tracing.py): always
        # constructed — every hook is a no-op attribute test when
        # trace_enabled is off, so the fault-free/trace-free path pays
        # nothing and the traced path touches no device state
        self.tracer = EngineTracer(
            enabled=paged.trace_enabled,
            buffer_steps=paged.trace_buffer_steps or 256,
        )
        if injector is not None:
            # fault firings become trace instants at the moment they fire
            injector.on_fire = self._trace_fault
        # checked (finite-verified) program variants: separate _programs
        # keys whose decode/verify traces add a (B,) poison-mask input and a
        # (B,) `finite` output; selected by the knob or implied by a chaos
        # plan that can fire nan faults
        self._check_logits = bool(
            paged.detect_nonfinite
            or (injector is not None and injector.wants("nan"))
        )
        # cached device-resident all-zeros poison mask: the checked
        # steady-state dispatch stays zero-upload (a mask uploads only on
        # the steps a nan fault actually fires)
        self._zero_mask = None
        # the declared compiled-program catalog (serving/catalog.py):
        # ladder × variant flags expanded into the exact legal key set of
        # the _programs registry — graftcheck GC007 audits every key
        # against it, prewarm() compiles it up front
        self.catalog = CatalogManifest.from_engine(self)
        # steady-state compile freeze (graftcheck GC008): mark_steady()
        # snapshots the registry keys; any later _register_program call
        # counts as a steady-state compile (gather-rung twins exempted
        # while the degradation ladder is active)
        self._frozen_keys: Optional[frozenset] = None
        self._prewarming = False
        if injector is not None:
            self.allocator.fault_hook = injector.alloc_fault
        # degradation ladder state (docs/serving.md): level 0 = everything
        # on; 1 sheds speculation, 2 the async lookahead, 3 the paged
        # kernel (gather fallback via a config-twin model), 4 preempt-sheds
        # the youngest lane on each further trip
        self._degrade_level = 0
        self._event_steps: deque = deque()  # step indices of recent events
        self._last_event_step = 0
        self._gather_model = None  # lazy use_paged_kernel=False twin
        # stall watchdog state
        self._step_index = 0
        self._stall_steps = 0
        self._last_progress_sig: Optional[tuple] = None
        # static pool-layout rows: under a tp mesh the kv-head-sharded pool
        # (paged_cache_specs) puts only NKV/tp heads on each chip, so the
        # same per-chip HBM holds a tp×-larger logical pool — the multi-chip
        # capacity win, made observable in every metrics snapshot. The bytes
        # are read off the pool's own leaves (payloads, scale tiles, states:
        # whatever the decode model's cache is made of), per rank off one
        # device's shards
        pool_leaves = jax.tree.leaves(self.cache)
        self.metrics.tp_size = parallel_state.tensor_parallel_size_or(1)
        self.metrics.kv_dtype = paged.kv_cache_dtype
        self.metrics.pool_bytes_total = sum(a.nbytes for a in pool_leaves)
        self.metrics.pool_bytes_per_rank = sum(
            a.addressable_shards[0].data.nbytes for a in pool_leaves
        )
        self.metrics.window_pool_blocks = sized.get("window_blocks", 0)

        self._next_rid = 0
        self._queue: List[_PagedRequest] = []
        self._active: Dict[int, _PagedRequest] = {}  # lane -> request
        self._finished: Dict[int, _PagedRequest] = {}
        # rid -> request, for O(1) request_info across every lifecycle state
        # (queued / active / prefilling / preempted / finished)
        self._requests: Dict[int, _PagedRequest] = {}
        self._free_lanes = list(range(engine.max_batch))
        self._key = jax.random.key(gen.seed)
        # host MIRRORS of the decode state — the scheduler reads these for
        # kv-bucket routing / block accounting; the authoritative copies
        # live on device (below) and are mutated by tiny jitted update
        # programs, never re-uploaded wholesale per step
        self._tokens = np.zeros((engine.max_batch,), np.int32)
        self._positions = np.zeros((engine.max_batch,), np.int32)
        self._tables = np.full(
            (engine.max_batch, self.table_width), NULL_BLOCK, np.int32
        )
        # device-RESIDENT decode state: every decode dispatch (sync or
        # async) consumes these arrays; the decode program writes its
        # sampled token and incremented position back into them, so a
        # steady-state step needs zero host→device transfers
        self._d_tokens = self._pin(jnp.asarray(self._tokens))
        self._d_positions = self._pin(jnp.asarray(self._positions))
        self._d_tables = self._pin(jnp.asarray(self._tables))
        # fused-sampling residents (PagedConfig.on_device_sampling): the
        # per-lane sampling params + raw PRNG key data ride next to
        # tokens/positions/tables — scattered by the same lane_set
        # program, consumed by every fused dispatch, never re-uploaded per
        # step. temperature <= 0 (GREEDY_TEMPERATURE) is the idle/greedy
        # sentinel; key data is raw uint32 because typed key arrays cannot
        # ride a donated scatter.
        self._temps = np.full(
            (engine.max_batch,), GREEDY_TEMPERATURE, np.float32
        )
        self._topks = np.zeros((engine.max_batch,), np.int32)
        self._topps = np.ones((engine.max_batch,), np.float32)
        self._rng = np.zeros((engine.max_batch, 2), np.uint32)
        self._d_temps = self._d_topks = self._d_topps = self._d_rng = None
        if self._fused:
            self._d_temps = self._pin(jnp.asarray(self._temps))
            self._d_topks = self._pin(jnp.asarray(self._topks))
            self._d_topps = self._pin(jnp.asarray(self._topps))
            self._d_rng = self._pin(jnp.asarray(self._rng))
        # advanced positions are clamped here: keeps a long-idle garbage
        # lane's position inside the rope table (see LlamaDecode.decode_step)
        self._pos_cap = self.table_width * bs - 1
        # lanes whose host-mirror state must be pushed to device before the
        # next dispatch (admitted / finished / preempted / installed lanes),
        # and single block-table entries appended by decode block growth
        self._dirty_lanes: set = set()
        self._table_delta_list: List[tuple] = []  # (lane, col, block_id)
        # depth-1 lookahead: the dispatched-but-unread decode step
        # (tokens device array, decode-lane snapshot, dispatch index)
        self._pending: Optional[tuple] = None
        self._dispatch_count = 0
        self._last_readback_lag = 0  # dispatches between dispatch and read
        self._wait_ms = 0.0          # per-step readback wait scratch
        self._last_log_step = 0      # dedupe periodic metrics logging
        self._last_prefill_bucket = 0  # bucket of the most recent prefill
        self._last_prefill_kv = 0      # its kv_limit rung; 0 = pctx, no cache read
        # its ``sparse_tiles_walked`` / ``sparse_tiles_rung`` where the model's
        # chunk read walks tiles (``chunk_tiles``), else nothing
        self._last_prefill_tiles: Dict[str, int] = {}
        self._programs: Dict[tuple, ProgramRecord] = {}
        # the block programs move whatever arrays the decode model's cache
        # is made of — k and v, their scale tiles under quantized storage
        # (the scale IS part of a block's value), or one latent row array —
        # at [:, block] of each: (L, num_blocks, block_size, ...)
        def _copy_block(c, s, d):
            return jax.tree.map(lambda a: a.at[:, d].set(a[:, s]), c)

        self._copy_block_fn = self._register_program(
            ("copy_block", self._kv_quantized), _copy_block,
            donate_argnums=(0,), kind="copy_block",
        )
        # tiered-KV spill programs, registered only when spill is on (the
        # registry must stay inside the catalog's key universe — GC007).
        # block_save slices one block's payload out of the pool: a pure
        # read, NOT donated, so its snapshot buffers stay valid after the
        # allocator reuses the id. block_restore scatters an uploaded
        # payload into a freshly allocated block, donating the pool like
        # copy_block does.
        self._block_save_fn = None
        self._block_restore_fn = None
        if self._spill:
            def _block_save(c, b):
                return tuple(a[:, b] for a in jax.tree.leaves(c))

            def _block_restore(c, b, *payload):
                leaves, treedef = jax.tree.flatten(c)
                return jax.tree.unflatten(
                    treedef, [a.at[:, b].set(x) for a, x in zip(leaves, payload)]
                )

            self._block_save_fn = self._register_program(
                ("block_save", self._kv_quantized), _block_save,
                kind="block_save",
            )
            self._block_restore_fn = self._register_program(
                ("block_restore", self._kv_quantized), _block_restore,
                donate_argnums=(0,), kind="block_restore",
            )
        # graftmeter device-cost ledger (serving/accounting.py): filled by
        # ensure_cost_profiles() — automatically at the end of prewarm()
        # when cost_accounting is on. _flops_by_key caches (flops, bytes)
        # per COMPUTE program key so the per-dispatch meter fold is two
        # float adds off a dict hit; move programs are profiled but never
        # counted into dispatched_flops (their "flops" are elements moved).
        self.cost_profiles: Optional[Dict[tuple, Any]] = None
        self.hbm: Optional[Any] = None
        self._flops_by_key: Dict[tuple, tuple] = {}
        from neuronx_distributed_llama3_2_tpu.flops import chip_peaks

        peaks = chip_peaks()
        self.metrics.peak_flops_per_chip = peaks.bf16_flops
        self.metrics.peak_hbm_bw_per_chip = peaks.hbm_bw
        # SLO burn-rate monitor (serving/slo.py): built only when an
        # objective is declared; otherwise the step hook is a None test
        slo_policy = SLOPolicy.from_paged(paged)
        self._slo: Optional[SLOMonitor] = (
            SLOMonitor(slo_policy, self.metrics) if slo_policy.active
            else None
        )
        # graftplan certified policy table (analysis/graftplan.py):
        # loaded before prewarm so a stale artifact fails fast, and
        # checked against *this* engine's completed ladders (GC011). A
        # caller-supplied policy instance that already carries a table
        # (certification harness) is re-checked the same way.
        # the artifact path is strict (a table from disk must carry a
        # fresh certificate); a caller-supplied instance's table is
        # advisory (stale gauge, no raise) so the certification harness
        # can run a not-yet-stamped candidate live.
        if paged.policy_table_path is not None:
            self.load_policy_table(paged.policy_table_path)
        elif getattr(self.policy, "table", None) is not None:
            self.load_policy_table(
                getattr(self.policy, "table"), strict=False
            )
        if paged.prewarm:
            self.prewarm()

    # -- programs ----------------------------------------------------------

    def _register_program(
        self,
        key_: tuple,
        fn,
        donate_argnums: tuple = (),
        kind: Optional[str] = None,
        gather: bool = False,
        checked: bool = False,
        **meta,
    ) -> ProgramRecord:
        """The single ``jax.jit`` site on the serving path: every program
        the engine dispatches is wrapped in a :class:`ProgramRecord` and
        cached in the ``_programs`` registry, so ``graftcheck``'s
        ``audit_programs`` can see (and re-lower / retrace) the complete
        compiled-program population. shardlint SL007 flags any donated
        jit in ``serving/`` created anywhere else. The program is traced
        inside a named scope of its kind (``pctx``, ``psfx``, ``pdecode``,
        ...), here and wherever graftcheck retraces ``rec.fn``."""
        kind = kind if kind is not None else str(key_[0])
        rec = ProgramRecord(
            key=key_,
            kind=kind,
            fn=_in_scope(kind, fn),
            donate_argnums=tuple(donate_argnums),
            gather=gather,
            checked=checked,
            meta=meta,
        )
        if self.tracer.enabled and kind in _LIVE_ROWS:
            rec.fn = _with_routing_tap(rec.fn, rec)
            rec.on_routed = self._note_routed
        rec.jitted = jax.jit(rec.fn, donate_argnums=donate_argnums)
        self._programs[key_] = rec
        self.metrics.programs_compiled += 1
        if self._prewarming:
            self.metrics.prewarm_compiles += 1
        elif self._frozen_keys is not None and not gather:
            # a compile after the steady-state freeze is a TTFT/TPOT
            # stall under real traffic — the runtime twin of graftcheck
            # GC008. Gather twins are exempt: the degradation ladder's
            # kernel-shed rung mints them deliberately on first climb.
            self.metrics.steadystate_compiles += 1
        return rec

    def _note_routed(self, rec: ProgramRecord, counts: jax.Array) -> None:
        self.tracer.routed(
            self._step_index, rec.kind, rec.routing["paths"],
            rec.routing["pairs_computed"], counts, rec.routing["held"],
        )

    def program_registry(self) -> Dict[tuple, ProgramRecord]:
        """key -> :class:`ProgramRecord` for every program this engine has
        built (the graftcheck audit surface; see ``audit_programs``)."""
        return dict(self._programs)

    def catalog_manifest(self) -> CatalogManifest:
        """The declared compiled-program catalog (serving/catalog.py) —
        static for the engine's lifetime; ``catalog.keys()`` is the GC007
        legality universe for :meth:`program_registry`."""
        return self.catalog

    def ensure_cost_profiles(self, deep: bool = False) -> Dict[tuple, Any]:
        """graftmeter harvest (serving/accounting.py): build per-program
        :class:`CostProfile`\\ s from every registered ``ProgramRecord``
        (XLA ``cost_analysis`` where a lowering exists, analytic formulas
        otherwise), the HBM ledger, the per-rung roofline table, and the
        per-key FLOP cache the dispatch meter folds from. Pure host work;
        runs automatically at the end of :meth:`prewarm` when
        ``PagedConfig.cost_accounting`` is on. ``deep=True`` additionally
        compiles each lowering for XLA ``temp_size_in_bytes`` (expensive —
        offline analysis only). Idempotent per (deep,) flavor."""
        from neuronx_distributed_llama3_2_tpu.serving.accounting import (
            COMPUTE_KINDS,
            harvest_cost_profiles,
            hbm_ledger,
        )

        with SETUP.span("setup.cost_profiles"):
            profiles = harvest_cost_profiles(self, deep=deep)
            self.cost_profiles = profiles
            self._flops_by_key = {
                k: (p.flops, p.bytes_accessed)
                for k, p in profiles.items()
                if p.kind in COMPUTE_KINDS
            }
            ledger = hbm_ledger(
                self, profiles=profiles,
                budget_bytes=self.paged.hbm_budget_bytes,
            )
            self.hbm = ledger
            m = self.metrics
            m.cost_profiled_programs = len(profiles)
            m.hbm_budget_bytes = ledger.budget_bytes
            m.hbm_footprint_bytes = ledger.footprint_bytes
            m.hbm_headroom_bytes = ledger.headroom_bytes
            # per-rung roofline ceilings from the plain (non-gather, unchecked)
            # decode profile of each kv rung: what MFU the memory system allows
            # a decode dispatch at that attention extent
            peak_flops = m.peak_flops_per_chip * max(m.tp_size, 1)
            peak_bw = m.peak_hbm_bw_per_chip * max(m.tp_size, 1)
            by_rung: Dict[int, dict] = {}
            for key_, p in profiles.items():
                if p.kind != "pdecode" or key_[3] or key_[4]:
                    continue
                rung = int(key_[2])
                by_rung[rung] = {
                    "flops": p.flops,
                    "bytes": p.bytes_accessed,
                    "arithmetic_intensity": round(p.arithmetic_intensity(), 6),
                    "roofline_mfu": round(
                        p.roofline_mfu(peak_flops, peak_bw), 6),
                }
            m.mfu_by_rung = by_rung
        return profiles

    def _setup_facts(self) -> Dict[str, int]:
        """What construction did, for the tracer's ``setup`` record: the
        fused weight leaves the engine re-placed and their bytes
        (inference/placement.py), and the largest ``temp_size_in_bytes``
        among the dispatched programs — which is where a per-layer copy of
        a weight shows — and the bytes a token leaves in the pool a layer
        (``inference.model.cache_row_bytes``), and between layers where the
        residual has several streams (``residual_row_bytes``). Each record is lowered as it was dispatched and
        compiled for its memory analysis (a persistent-cache hit where
        there is a cache, a compile where there is none), so traced
        engines only."""
        from neuronx_distributed_llama3_2_tpu.serving.accounting import (
            harvest_cost_profiles,
        )

        with SETUP.span("setup.facts"):
            profiles = harvest_cost_profiles(self, deep=True)
            return {
                "relaid_leaves": self.engine.placement["leaves"],
                "relaid_bytes": self.engine.placement["bytes"],
                "program_temp_bytes_max": max(
                    (p.temp_bytes for p in profiles.values()), default=0
                ),
                # rows by position: bytes a token a layer; a state: bytes a
                # lane holds of it over all its layers — one block of a cache
                # that is a state, one slot of a kind that is
                **({"cache_row_bytes": cache_row_bytes(self._kind_pool(self.model.cache_kinds[0]))}
                   if self._positional else {}),
                **({"state_bytes_per_lane": cache_block_bytes(
                    self._kind_pool(self._lane_kind) if self._positional else self.cache)}
                   if self._has_state else {}),
                # a multi-stream residual (models/xing.py): bytes a token's
                # streams take between layers
                **({"residual_row_bytes": self.model.residual_row_bytes()}
                   if self.model.residual_row_bytes() is not None else {}),
                **self._kind_facts(),
            }

    def _kind_pool(self, kind):
        """The part of the cache that holds ``kind``: the field of its name
        where the cache has fields by kind, else all of it."""
        return getattr(self.cache, kind.name, self.cache)

    def _kind_facts(self) -> Dict[str, Any]:
        """``cache_kinds`` of the ``setup`` record — a kind: its layers, the
        rows a lane keeps of it (null = the whole context, 0 = none: a state),
        a row's bytes a layer — or a state's over the kind's layers — as the
        device lays them out, and which read a decode step takes of it
        (``decode_read``: ``"kernel"``, ``"gather"`` or ``"pass"``; a state
        kind also how a prefill's block of rows goes through it, ``chunk_scan``:
        ``"kernel"`` or ``"loop"``; a kind of rows whose layers read a block
        of rows under a block mask also how, ``chunk_read``: ``"kernel"`` or
        ``"tiles"``) — and ``window_ring_rows``; nothing where the whole cache
        is a state."""
        if not self._positional:
            return {}
        ring_rows = 0 if self._has_state else self._lane_blocks * self.paged.block_size
        chunk_read = self.model.chunk_read()
        return {
            "cache_kinds": {
                kind.name: {
                    "layers": kind.layers,
                    "rows_per_lane": None if kind.rows is None else ring_rows,
                    **({"state_bytes": cache_block_bytes(self._kind_pool(kind)),
                        "chunk_scan": self.model.chunk_scan()} if kind.state
                       else {"row_bytes": cache_row_bytes(self._kind_pool(kind))}),
                    "decode_read": self.model.decode_read(
                        kind, self._kind_pool(kind).quantized),
                    **({"chunk_read": chunk_read} if chunk_read and not kind.state else {}),
                }
                for kind in self.model.cache_kinds
            },
            "window_ring_rows": ring_rows,
        }

    def _decode_rows(self, decode_lanes) -> Dict[str, int]:
        """A decode dispatch record's ``rows``: the cache rows the live lanes
        attend over, this step's included — or, where the cache is a state a
        lane, the live lanes: the states the step has to move. Where a kind of
        layer sees only its last rows, ``window_rows`` beside it: the rows
        those layers attend over, min(context, window) a live lane, and
        ``window_rows_passed`` — the rows the dispatched program reads of that
        kind a layer: where its ``decode_read`` is ``"kernel"``, the blocks the
        walk takes (the window's first row's to the lane's own) in rows, a live
        lane, and one block a lane that is not; else every lane's whole ring.
        Where a kind is a state, ``state_lanes`` — the live lanes — and
        ``state_slots_passed`` — the slots the dispatched program reads and
        writes: the live lanes' where it holds the state kernel, else every
        slot of the kind's pool, a live lane's or not. Where a kind's layers choose the blocks
        they read (``selected_rows``), ``sparse_rows_cached`` /
        ``sparse_rows_read`` / ``sparse_blocks_forced``, a layer's, summed over
        the live lanes."""
        if not self._positional:
            return {"rows": len(decode_lanes)}
        contexts = [int(self._positions[l]) + 1 for l in decode_lanes]
        rows = {"rows": sum(contexts)}
        if self._has_state:
            rows["state_lanes"] = len(decode_lanes)
            rows["state_slots_passed"] = (
                len(decode_lanes) if self._state_kernel
                else 1 + self.engine.max_batch * self._lane_blocks)
            if contexts and self.model.selected_rows(contexts[0]) is not None:
                selected = [self.model.selected_rows(n) for n in contexts]
                # layers that read only the blocks they choose: a layer's
                # rows the live lanes hold, the rows it reads of them, and the
                # blocks among those it took unscored (the first, the window)
                rows["sparse_rows_cached"] = sum(contexts)
                rows["sparse_rows_read"] = sum(read for read, _ in selected)
                rows["sparse_blocks_forced"] = sum(forced for _, forced in selected)
        elif self._lane_kind is not None:
            window, bs = self._lane_kind.rows, self.paged.block_size
            rows["window_rows"] = sum(min(n, window) for n in contexts)
            if self.model.decode_read(self._lane_kind, self._kv_quantized) == "kernel":
                walked = sum((n - 1) // bs - max(0, n - window) // bs + 1 for n in contexts)
                rows["window_rows_passed"] = bs * (
                    walked + self.engine.max_batch - len(decode_lanes))
            else:
                rows["window_rows_passed"] = self.engine.max_batch * self._lane_blocks * bs
        return rows

    def _kv_bucket(self, needed: int) -> int:
        """kv_limit rung covering ``needed`` rows over the serving kv
        ladder (``PagedConfig.kv_buckets`` or the InferenceEngine's
        buckets) — the serving twin of ``InferenceEngine._kv_bucket``,
        with the same clamp-to-full-cache fallback past the ladder top
        (verify write frontiers may briefly exceed max_seq_len)."""
        for b in self._kv_buckets:
            if b >= needed:
                return b
        return self._kv_buckets[-1]

    def mark_steady(self) -> None:
        """Freeze the compiled-program registry: graftcheck GC008 flags
        any key added — or re-lowered at new avals — after this point
        (gather twins exempted while the degradation ladder is active),
        and later compiles count in ``metrics.steadystate_compiles``.
        Called automatically at the end of :meth:`prewarm`; a soak
        harness warming up through real traffic instead can call it once
        its working set has compiled."""
        with SETUP.span("setup.mark_steady"):
            self._frozen_keys = frozenset(self._programs)

    def _step_model(self):
        """The model instance new program traces bind: normally
        ``self.model``; at degradation-ladder level >= 3 a lazily built
        ``use_paged_kernel=False`` config twin, so every program compiled
        on that rung takes the dense-gather fallback instead of the Pallas
        kernel. The twin holds no weights (params ride in per call) and the
        cache layout is identical, so switching rungs only changes which
        cached program a dispatch picks."""
        if self._degrade_level >= 3 and getattr(
            self.model.config, "use_paged_kernel", False
        ):
            if self._gather_model is None:
                self._gather_model = type(self.model)(
                    dataclasses.replace(self.model.config, use_paged_kernel=False)
                )
            return self._gather_model
        return self.model

    def _gather_shed(self) -> bool:
        """Program-cache key bit for the kernel-shed rung."""
        return self._step_model() is not self.model

    def _decode_cfg(self):
        """The sampling slot of pctx/psfx/pdecode program keys: the static
        :class:`SamplingConfig` on the host-sampling path, the literal
        ``"lane"`` sentinel under fused on-device sampling — per-lane
        params are runtime arrays there, so ONE compiled program serves
        every sampling config (and the catalog shrinks accordingly)."""
        return "lane" if self._fused else self.gen.sampling

    def _prefill_table(self, table, lane: Optional[int]) -> np.ndarray:
        """The (1, W) table a prefill program takes: the request's blocks,
        null past them — and, where a kind of the cache is laid out a lane,
        the lane's ring or slot after the ``table_width`` columns
        (:meth:`_table_kinds` parts them again inside the program). ``lane``
        None: a warm-up call, whose rows all land in the null blocks."""
        row = np.full((1, self.table_width + self._lane_blocks), NULL_BLOCK, np.int32)
        row[0, : len(table)] = table
        if self._lane_blocks and lane is not None:
            row[0, self.table_width:] = self._lane_tables[lane]
        return row

    def _table_kinds(self, table) -> Dict[str, Any]:
        """A prefill program's table as the model's ``forward`` takes it: the
        block table, and the lane's ring or slot as ``<kind>_tables`` where a
        kind is laid out a lane."""
        if not self._lane_blocks:
            return {"block_tables": table}
        return {
            "block_tables": table[:, : self.table_width],
            f"{self._lane_kind.name}_tables": table[:, self.table_width:],
        }

    def _prefill_ctx_program(self, bucket: int, cfg):
        """Whole-prompt prefill (no cached prefix): context-encode forward +
        last-token gather + on-device sample, paged writes. Under fused
        sampling (``cfg == "lane"``) the host PRNG key argument is replaced
        by the admitted request's (1, 2) key data + (1,) sampling params and
        the draw is keyed by the landing index (= the prefilled length)."""
        key_ = ("pctx", bucket, cfg, self._gather_shed())
        if key_ in self._programs:
            return self._programs[key_]
        model, engine = self._step_model(), self.engine

        # a state keeps what a padded row does to it: the live length reaches
        # the model (None leaves a positional model's lowering as it was)
        positional = not self._has_state

        def _last_logits(params, cache, ids, positions, length, table):
            hidden, cache = model.forward(
                params, cache, ids, positions, None,
                context_encode=True, return_hidden=True,
                row_live=None if positional else length,
                **self._table_kinds(table),
            )
            last = jnp.take_along_axis(
                hidden, (length - 1)[:, None, None], axis=1
            )
            return model._model()._logits(params, last)[:, 0, :], cache

        if self._fused:
            def fn(params, cache, ids, length, table, rng, temp, topk, topp):
                params = engine._live_params(params)
                positions = jnp.zeros((ids.shape[0],), jnp.int32)
                logits, cache = _last_logits(
                    params, cache, ids, positions, length, table
                )
                # the sampled token lands at sequence index `length` —
                # the same fold_in index a decode step at position
                # length - 1 would use, so resume replays identically
                tok = sample_lanes(logits, rng, length, temp, topk, topp)
                return tok, cache
        else:
            def fn(params, cache, ids, length, table, key):
                params = engine._live_params(params)
                positions = jnp.zeros((ids.shape[0],), jnp.int32)
                logits, cache = _last_logits(
                    params, cache, ids, positions, length, table
                )
                return sample(logits, key, cfg), cache

        return self._register_program(
            key_, fn, donate_argnums=(1,), kind="pctx",
            gather=self._gather_shed(), bucket=bucket,
        )

    def _prefill_suffix_program(self, bucket: int, kv_limit: int, cfg):
        """Suffix prefill after a prefix-cache hit: the fresh block starts at
        position ``start`` (the cached length) and attends over the shared
        prefix blocks through the table — the cached tokens are never
        recomputed. Fused sampling keys the draw by ``start + length`` (the
        landing index of the sampled token); non-final chunked-prefill
        dispatches discard their token, so only the final chunk's index —
        the total committed length — ever reaches a stream."""
        key_ = ("psfx", bucket, kv_limit, cfg, self._gather_shed())
        if key_ in self._programs:
            return self._programs[key_]
        model, engine = self._step_model(), self.engine

        positional = not self._has_state

        def _last_logits(params, cache, ids, start, length, table):
            hidden, cache = model.forward(
                params, cache, ids, start, None,
                return_hidden=True, kv_limit=kv_limit,
                row_live=None if positional else length,
                **self._table_kinds(table),
            )
            last = jnp.take_along_axis(
                hidden, (length - 1)[:, None, None], axis=1
            )
            return model._model()._logits(params, last)[:, 0, :], cache

        if self._fused:
            def fn(params, cache, ids, start, length, table,
                   rng, temp, topk, topp):
                params = engine._live_params(params)
                logits, cache = _last_logits(
                    params, cache, ids, start, length, table
                )
                tok = sample_lanes(
                    logits, rng, start + length, temp, topk, topp
                )
                return tok, cache
        else:
            def fn(params, cache, ids, start, length, table, key):
                params = engine._live_params(params)
                logits, cache = _last_logits(
                    params, cache, ids, start, length, table
                )
                return sample(logits, key, cfg), cache

        return self._register_program(
            key_, fn, donate_argnums=(1,), kind="psfx",
            gather=self._gather_shed(), bucket=bucket, kv_limit=kv_limit,
        )

    def _decode_program(self, cfg, kv_limit: int):
        """Resident-state decode: one T=1 step over the device-resident
        (tokens, positions, tables), returning the sampled tokens and the
        advanced positions so step N+1 can dispatch with NO host input.
        The cache and positions are donated (overwritten in place); tokens
        are NOT — the previous step's sampled-token array must stay alive
        for its (lagging) host readback while already feeding this
        dispatch.

        The checked variant (``PagedConfig.detect_nonfinite`` / a nan-fault
        chaos plan) adds a (B,) int32 poison-mask input and a (B,) bool
        ``finite`` output via ``finite_logit_check`` — detection runs on
        device and one bool per lane rides the existing readback. A
        separate program key: the unchecked trace stays bitwise unchanged.

        The fused variant (``cfg == "lane"``) takes the four sampling
        residents instead of a host PRNG key — the WHOLE argument list is
        then device-resident, which is what makes *sampled* steady-state
        decode genuinely zero-upload — and delegates the draw (and the
        checked finite gate) to ``LlamaDecode.decode_step(sampling=)``."""
        checked = self._check_logits
        key_ = ("pdecode", cfg, kv_limit, self._gather_shed(), checked)
        if key_ in self._programs:
            return self._programs[key_]
        model, engine = self._step_model(), self.engine
        pos_cap = self._pos_cap
        # every lane's ring or slot, where a kind of the cache is laid out a
        # lane: laid out at construction and never changed, so a constant of
        # the program
        ring = {} if self._lane_tables is None else {
            f"{self._lane_kind.name}_tables": self._lane_tables}

        if self._fused and checked:
            def fn(params, cache, tokens, positions, tables,
                   temp, topk, topp, rng, nan_mask):
                params = engine._live_params(params)
                return model.decode_step(
                    params, cache, tokens, positions, tables,
                    kv_limit=kv_limit, pos_cap=pos_cap,
                    sampling=(rng, temp, topk, topp), logit_poison=nan_mask, **ring,
                )
        elif self._fused:
            def fn(params, cache, tokens, positions, tables,
                   temp, topk, topp, rng):
                params = engine._live_params(params)
                return model.decode_step(
                    params, cache, tokens, positions, tables,
                    kv_limit=kv_limit, pos_cap=pos_cap,
                    sampling=(rng, temp, topk, topp), **ring,
                )
        elif checked:
            def fn(params, cache, tokens, positions, tables, key, nan_mask):
                params = engine._live_params(params)
                logits, new_positions, cache = model.decode_step(
                    params, cache, tokens, positions, tables,
                    kv_limit=kv_limit, pos_cap=pos_cap, **ring,
                )
                logits, finite = model.finite_logit_check(logits, nan_mask)
                return sample(logits, key, cfg), finite, new_positions, cache
        else:
            def fn(params, cache, tokens, positions, tables, key):
                params = engine._live_params(params)
                logits, new_positions, cache = model.decode_step(
                    params, cache, tokens, positions, tables,
                    kv_limit=kv_limit, pos_cap=pos_cap, **ring,
                )
                return sample(logits, key, cfg), new_positions, cache

        return self._register_program(
            key_, fn, donate_argnums=(1, 3), kind="pdecode",
            gather=self._gather_shed(), checked=checked, kv_limit=kv_limit,
        )

    def _verify_program(self, kv_limit: int, k: int):
        """Speculative verify: score the per-lane candidate block
        ``[resident token, d_0 .. d_{k-1}]`` in one T = k+1 forward and
        advance the resident state by the on-device accept length
        (``LlamaDecode.verify_step``). Cache and positions are donated like
        the plain decode program; the resident token array is not (it may
        still be a pending readback source) — the fresh drafts ride in as a
        separate (B, k) upload, the ONLY per-step host→device traffic
        speculation adds. Checked variant: poison mask in, trailing
        ``finite`` out, applied *before* the accept rule (see
        ``LlamaDecode.verify_step``). The fused-sampling variant appends
        the four sampling residents and the accept targets become
        position-keyed draws — the sampled-verify path the greedy-only
        guard used to forbid."""
        checked = self._check_logits
        key_ = ("pverify", kv_limit, k, self._gather_shed(), checked)
        if key_ in self._programs:
            return self._programs[key_]
        model, engine = self._step_model(), self.engine
        pos_cap = self._pos_cap

        if self._fused and checked:
            def fn(params, cache, tokens, positions, tables, drafts,
                   draft_len, temp, topk, topp, rng, nan_mask):
                params = engine._live_params(params)
                block = jnp.concatenate([tokens[:, None], drafts], axis=1)
                return model.verify_step(
                    params, cache, block, positions, tables, draft_len,
                    kv_limit=kv_limit, pos_cap=pos_cap,
                    sampling=(rng, temp, topk, topp), logit_poison=nan_mask,
                )
        elif self._fused:
            def fn(params, cache, tokens, positions, tables, drafts,
                   draft_len, temp, topk, topp, rng):
                params = engine._live_params(params)
                block = jnp.concatenate([tokens[:, None], drafts], axis=1)
                return model.verify_step(
                    params, cache, block, positions, tables, draft_len,
                    kv_limit=kv_limit, pos_cap=pos_cap,
                    sampling=(rng, temp, topk, topp),
                )
        elif checked:
            def fn(params, cache, tokens, positions, tables, drafts,
                   draft_len, nan_mask):
                params = engine._live_params(params)
                block = jnp.concatenate([tokens[:, None], drafts], axis=1)
                return model.verify_step(
                    params, cache, block, positions, tables, draft_len,
                    kv_limit=kv_limit, pos_cap=pos_cap, logit_poison=nan_mask,
                )
        else:
            def fn(params, cache, tokens, positions, tables, drafts, draft_len):
                params = engine._live_params(params)
                block = jnp.concatenate([tokens[:, None], drafts], axis=1)
                return model.verify_step(
                    params, cache, block, positions, tables, draft_len,
                    kv_limit=kv_limit, pos_cap=pos_cap,
                )

        return self._register_program(
            key_, fn, donate_argnums=(1, 3), kind="pverify",
            gather=self._gather_shed(), checked=checked,
            kv_limit=kv_limit, k=k,
        )

    def _tree_program(self, kv_limit: int, k: int):
        """Tree-speculative verify (``PagedConfig.spec_tree``): score a
        packed candidate TREE of k draft nodes rooted at the resident
        token in one ancestor-masked T = k+1 forward, accept the deepest
        root-anchored path on device and relocate its K/V rows to the
        true frontier (``LlamaDecode.tree_verify_step``). The whole draft
        — node tokens, tree topology and per-lane live-node count — rides
        in as ONE packed (B, 2k+1) int32 upload
        ``[drafts(k) | parents(k) | live_draft_nodes(1)]``, one fewer
        metered upload than the linear verify's drafts + draft_len pair,
        so tree speculation fits the same ≤2-upload verify budget.
        Donation, checked and fused-sampling variants mirror
        ``_verify_program`` exactly; a lane whose drafter abstained
        carries zero live nodes and takes a plain decode step."""
        checked = self._check_logits
        key_ = ("ptree", kv_limit, k, self._gather_shed(), checked)
        if key_ in self._programs:
            return self._programs[key_]
        model, engine = self._step_model(), self.engine
        pos_cap = self._pos_cap

        def unpack(tokens, payload):
            drafts = payload[:, :k]
            parents = jnp.concatenate(
                [jnp.zeros_like(payload[:, :1]), payload[:, k : 2 * k]],
                axis=1,
            )
            node_len = payload[:, 2 * k] + 1  # root is always live
            block = jnp.concatenate([tokens[:, None], drafts], axis=1)
            return block, parents, node_len

        if self._fused and checked:
            def fn(params, cache, tokens, positions, tables, payload,
                   temp, topk, topp, rng, nan_mask):
                params = engine._live_params(params)
                block, parents, node_len = unpack(tokens, payload)
                return model.tree_verify_step(
                    params, cache, block, positions, tables, parents,
                    node_len, kv_limit=kv_limit, pos_cap=pos_cap,
                    sampling=(rng, temp, topk, topp), logit_poison=nan_mask,
                )
        elif self._fused:
            def fn(params, cache, tokens, positions, tables, payload,
                   temp, topk, topp, rng):
                params = engine._live_params(params)
                block, parents, node_len = unpack(tokens, payload)
                return model.tree_verify_step(
                    params, cache, block, positions, tables, parents,
                    node_len, kv_limit=kv_limit, pos_cap=pos_cap,
                    sampling=(rng, temp, topk, topp),
                )
        elif checked:
            def fn(params, cache, tokens, positions, tables, payload,
                   nan_mask):
                params = engine._live_params(params)
                block, parents, node_len = unpack(tokens, payload)
                return model.tree_verify_step(
                    params, cache, block, positions, tables, parents,
                    node_len, kv_limit=kv_limit, pos_cap=pos_cap,
                    logit_poison=nan_mask,
                )
        else:
            def fn(params, cache, tokens, positions, tables, payload):
                params = engine._live_params(params)
                block, parents, node_len = unpack(tokens, payload)
                return model.tree_verify_step(
                    params, cache, block, positions, tables, parents,
                    node_len, kv_limit=kv_limit, pos_cap=pos_cap,
                )

        return self._register_program(
            key_, fn, donate_argnums=(1, 3), kind="ptree",
            gather=self._gather_shed(), checked=checked,
            kv_limit=kv_limit, k=k,
        )

    def _mixed_program(self, t: int, kv_limit: int):
        """Fused mixed-mode step (``PagedConfig.fused_step``): ONE t-row
        program serving every lane role at once — decode lanes ride as a
        ``[resident token, drafts...]`` verify block (draft_len 0 is a
        plain decode row), prefilling lanes as *forced* rows carrying this
        step's chunk suffix, sampled/argmaxed at the chunk's last live row
        exactly like the psfx program (``LlamaDecode.mixed_step``). Cache
        and positions are donated like decode/verify; the per-step row
        payload (rows/row_start/row_len/forced) uploads like verify's
        drafts — prefill traffic always paid per-call uploads, and the
        pure-decode steady state never dispatches this kind (GC003 holds).
        Fused-sampling and checked variants mirror ``_verify_program``.

        Under ``spec_tree`` the verify rows carry a packed tree: a per-lane
        ``parents`` operand rides immediately after ``forced`` and
        ``LlamaDecode.mixed_step`` steers forced lanes onto the
        single-chain topology, so chunk semantics (and the key) are
        unchanged — the tree flavor is engine-scoped, not a new rung."""
        checked = self._check_logits
        cfg = self._decode_cfg()
        key_ = ("pmixed", t, kv_limit, cfg, self._gather_shed(), checked)
        if key_ in self._programs:
            return self._programs[key_]
        model, engine = self._step_model(), self.engine
        pos_cap = self._pos_cap
        fused, spec_tree = self._fused, self._spec_tree

        def fn(params, cache, tokens, positions, tables, rows,
               row_start, row_len, forced, *tail):
            params = engine._live_params(params)
            tail = list(tail)
            kw = dict(kv_limit=kv_limit, pos_cap=pos_cap)
            if spec_tree:
                kw["parents"] = tail.pop(0)
            if fused:
                temp, topk, topp, rng = tail[:4]
                tail = tail[4:]
                kw["sampling"] = (rng, temp, topk, topp)
            if checked:
                kw["logit_poison"] = tail.pop(0)
            return model.mixed_step(
                params, cache, tokens, positions, tables,
                rows, row_start, row_len, forced, **kw,
            )

        return self._register_program(
            key_, fn, donate_argnums=(1, 3), kind="pmixed",
            gather=self._gather_shed(), checked=checked,
            kv_limit=kv_limit, t=t,
        )

    def _lane_set_program(self):
        """Full-lane resident-state update: scatter one lane's (token,
        position, table row) into the device arrays — the admission /
        finish / preemption path. All three residents are donated, so the
        update is an in-place dynamic-update-slice, not a reallocation.
        Only legal while no lookahead step is in flight (the donated token
        buffer could be the pending readback).

        Under fused sampling the same key scatters SEVEN residents — the
        per-lane sampling params and PRNG key data mutate ONLY through
        this donated path, which is what keeps sampled steady-state
        dispatches upload-free."""
        key_ = ("lane_set",)
        if key_ in self._programs:
            return self._programs[key_]

        if self._fused:
            def fn(tokens, positions, tables, temps, topks, topps, rng,
                   lane, tok, pos, trow, temp, topk, topp, rg):
                return (
                    tokens.at[lane].set(tok),
                    positions.at[lane].set(pos),
                    tables.at[lane].set(trow),
                    temps.at[lane].set(temp),
                    topks.at[lane].set(topk),
                    topps.at[lane].set(topp),
                    rng.at[lane].set(rg),
                )

            return self._register_program(
                key_, fn, donate_argnums=(0, 1, 2, 3, 4, 5, 6),
                kind="lane_set",
            )

        def fn(tokens, positions, tables, lane, tok, pos, trow):
            return (
                tokens.at[lane].set(tok),
                positions.at[lane].set(pos),
                tables.at[lane].set(trow),
            )

        return self._register_program(
            key_, fn, donate_argnums=(0, 1, 2), kind="lane_set"
        )

    def _table_delta_program(self):
        """Single-entry block-table scatter: decode growth appends one
        block id per boundary crossing; only ``tables`` is touched (and
        donated), so this is safe to run while a lookahead step is in
        flight."""
        key_ = ("table_delta",)
        if key_ in self._programs:
            return self._programs[key_]

        def fn(tables, lane, col, val):
            return tables.at[lane, col].set(val)

        return self._register_program(
            key_, fn, donate_argnums=(0,), kind="table_delta"
        )

    # -- host<->device choke points ---------------------------------------

    def _pin(self, x):
        """Commit a freshly constructed device-RESIDENT array to the
        mesh-replicated sharding the engine programs produce for it. Under
        a multi-chip mesh an uncommitted single-device array and a
        committed replicated one are *different lowerings* to jit, so a
        resident constructed without this pays one re-lower per program
        on its second dispatch (the recompile class GC008 exists to
        catch).

        Always copies, even off-mesh: on CPU backends ``jnp.asarray`` of
        a numpy array can ZERO-COPY alias the host buffer, and the first
        donated dispatch then writes its output straight through the
        alias into the engine's host mirror — nondeterministic
        frontier-lag corruption, caught by graftsched's per-action
        explorer audits. The copy severs the alias so donation can only
        ever recycle device-owned storage."""
        pinned = jnp.array(x, copy=True)
        if self._resident_sharding is None:
            return pinned
        return jax.device_put(pinned, self._resident_sharding)

    def _upload(self, x, dtype=jnp.int32):
        """Every host→device transfer on the serving path funnels through
        here so the steady-state zero-upload property is countable (and
        testable) — and so chaos latency spikes hit every transfer."""
        if self.injector is not None:
            self.injector.maybe_latency("upload")
        self.metrics.h2d_uploads += 1
        return jnp.asarray(x, dtype)

    def _read_tokens(self, toks) -> np.ndarray:
        """Every device→host token readback funnels through here: one
        conversion, with the blocking wait accounted as device time
        (``ServingMetrics.device_wait_ms``)."""
        if self.injector is not None:
            self.injector.maybe_latency("read")
        t0 = time.perf_counter()
        arr = read_host_tokens(toks)
        t1 = time.perf_counter()
        self._wait_ms += (t1 - t0) * 1e3
        if self.tracer.enabled:
            self.tracer.complete("readback", t0, t1, n=int(arr.size))
        return arr

    def _emit_action(self, atype: ActionType, mode: str = "", **meta) -> None:
        """Record one executed step-action into the graftsched action
        trace (host-only, bounded by the per-step ring). Policy-yielded
        actions are recorded by their executors; engine-internal
        transitions (PREEMPT/FINISH/flushes) funnel through here from the
        methods that perform them, so the trace is a faithful schedule of
        what actually ran — not of what the policy asked for."""
        rec = StepAction(atype, mode, meta)
        self._step_actions.append(rec)
        cb = self._on_action
        if cb is not None:
            cb(self, rec)

    # -- fused-sampling lane state (PagedConfig.on_device_sampling) --------

    def _lane_rng(self, rid: int) -> np.ndarray:
        """Per-request base PRNG key data (2,) uint32, derived from
        ``(gen.seed, rid)`` via SeedSequence: a preempted request
        re-installs the SAME key on re-admission, and with every draw
        keyed by its landing index (``sample_lanes``' fold_in discipline)
        the resumed stream replays the unpreempted run token for token."""
        return np.random.SeedSequence(
            [int(self.gen.seed), int(rid)]
        ).generate_state(2).astype(np.uint32)

    def _sampling_mode(self) -> str:
        """Tracer label + counter bucket for a decode/verify dispatch:
        ``"greedy"`` (argmax — either engine mode), ``"fused"`` (on-device
        sampled draw from the residents), or ``"host"`` (host-keyed
        sampled draw, the upload-paying fallback)."""
        if self.gen.sampling.greedy:
            return "greedy"
        return "fused" if self._fused else "host"

    def _note_sampling_dispatch(self) -> str:
        mode = self._sampling_mode()
        if mode == "fused":
            self.metrics.sampled_steps += 1
        elif mode == "host":
            self.metrics.host_sample_fallbacks += 1
        return mode

    def _install_lane_sampling(self, lane: int, req: _PagedRequest) -> None:
        """Admission-time host-mirror install of a lane's sampling params
        and base key (pushed to device by the next lane_set flush). A
        greedy GenerationConfig installs the temperature sentinel, so the
        fused program reduces to exact argmax for the lane."""
        if not self._fused:
            return
        s = self.gen.sampling
        if s.greedy:
            self._temps[lane] = GREEDY_TEMPERATURE
            self._topks[lane] = 0
            self._topps[lane] = 1.0
        else:
            self._temps[lane] = s.temperature
            self._topks[lane] = s.top_k
            self._topps[lane] = s.top_p
        self._rng[lane] = self._lane_rng(req.rid)
        self.metrics.rng_reseeds += 1

    def _clear_lane_sampling(self, lane: int) -> None:
        """Teardown twin of :meth:`_install_lane_sampling`: park the lane
        at the greedy sentinel with a null key — idle lanes keep stepping
        in the resident batch, and argmax is the cheapest garbage draw."""
        if not self._fused:
            return
        self._temps[lane] = GREEDY_TEMPERATURE
        self._topks[lane] = 0
        self._topps[lane] = 1.0
        self._rng[lane] = 0

    def _lane_sampling_args(self, lane: int) -> tuple:
        """``(rng (1, 2), temp (1,), topk (1,), topp (1,))`` uploads for a
        fused prefill dispatch — prefill pays per-call uploads anyway
        (ids/length/table); only decode/verify must stay resident-only."""
        return (
            self._upload(self._rng[lane: lane + 1], jnp.uint32),
            self._upload(self._temps[lane: lane + 1], jnp.float32),
            self._upload(self._topks[lane: lane + 1], jnp.int32),
            self._upload(self._topps[lane: lane + 1], jnp.float32),
        )

    # -- fault handling (docs/serving.md "Failure handling & degradation") --

    def _chaos_device(self, site: str, lanes: Sequence[int]) -> None:
        """Chaos funnel in front of a device program dispatch. Raising
        *before* the call is what makes recovery tractable: the donated
        cache and resident arrays are never half-mutated, so failing the
        victim lane and redispatching the survivors is always sound. (A
        *real* exception escaping a dispatch still propagates — after a
        genuine mid-execution failure the donated buffers are gone and no
        lane-scoped recovery is possible.)"""
        if self.injector is None:
            return
        victim = self.injector.device_fault(site, lanes)
        if victim is not None:
            raise InjectedFault("device", site, lanes=(victim,))

    def _nan_mask(self, lanes: Sequence[int], site: str):
        """(B,) int32 poison mask for a checked dispatch: the cached
        device-resident zeros array on clean steps (zero uploads), a fresh
        upload only when the injector fires a nan fault."""
        poison = (
            self.injector.nan_lanes(site, lanes)
            if self.injector is not None
            else []
        )
        if not poison:
            if self._zero_mask is None:
                self._zero_mask = jnp.zeros(
                    (self.engine.max_batch,), jnp.int32
                )
            return self._zero_mask
        m = np.zeros((self.engine.max_batch,), np.int32)
        m[poison] = 1
        return self._upload(m)

    def _release_lane(self, req: _PagedRequest) -> None:
        """THE lane-teardown funnel (finish / fail / preempt): release the
        request's blocks and null the lane's host mirrors, marking the
        lane dirty for the next full-lane sync. Only legal with no
        lookahead in flight — the callers drain first. One of the blessed
        host-mirror writers shardlint SL008 admits; teardown mirror writes
        anywhere else are findings."""
        lane = req.lane
        for b in req.table:
            self.allocator.release(b)
        req.table = []
        req.table_dev = None
        del self._active[lane]
        self._free_lanes.append(lane)
        self._tables[lane, :] = NULL_BLOCK
        self._tokens[lane] = 0
        self._positions[lane] = 0
        self._clear_lane_sampling(lane)
        self._dirty_lanes.add(lane)
        req.lane = None

    def _fail_request(self, req: _PagedRequest, error: str) -> None:
        """Terminal failure — the per-request failure domain. Mirrors
        ``_preempt``'s teardown (blocks released, lane freed, mirrors
        nulled + marked dirty for the next full-lane sync) but the request
        never re-queues: it lands in ``_finished`` with ``failed=True``,
        partial output intact, and ``error`` carrying the detail
        (``request_info`` surfaces both). Nothing is registered in the
        prefix index — a failed lane's tail blocks may hold garbage KV.
        Only legal with no lookahead in flight (callers drain first)."""
        assert self._pending is None, "failing a lane with a step in flight"
        if req.rid in self._finished:
            return
        req.failed = True
        req.done = True
        req.error = str(error)
        if req in self._queue:
            self._queue.remove(req)
        if req.lane is not None:
            lane = req.lane
            req.prefilling = False
            self._release_lane(req)
            self._emit_action(
                ActionType.FINISH, rid=req.rid, lane=lane, failed=True,
            )
        self._finished[req.rid] = req
        self.metrics.failed_requests += 1
        self._note_terminal(req)
        self.tracer.instant(
            "request_failed", rid=req.rid, error=req.error[:160]
        )
        self.tracer.request_state(req.rid, "failed")
        self._note_event()
        logger.warning(
            "request %d failed after %d tokens: %s",
            req.rid, len(req.out), req.error,
        )
        if self.paged.audit_debug:
            self._audit(strict=True)

    def _quarantine(self, req: _PagedRequest, site: str) -> None:
        """Non-finite logits detected on this lane: its sampled token (and
        any KV written from it) is garbage — fail the request instead of
        committing. Companion lanes are untouched: per-lane attention means
        their logits never saw the poisoned lane."""
        self.metrics.lane_quarantines += 1
        self._fail_request(
            req, f"non-finite logits at {site} step (lane quarantined)"
        )

    def _recover_fault(self, fault: InjectedFault) -> bool:
        """A device fault surfaced from a dispatch funnel: retire the
        in-flight lookahead (its tokens are valid — it dispatched before
        the fault), fail the victim lanes' requests, and keep serving.
        Survivor lanes redispatch next step from untouched resident state."""
        self._drain_pending()
        failed_any = False
        for lane in fault.lanes:
            req = self._active.get(lane)
            if req is not None:
                self._fail_request(req, str(fault))
                failed_any = True
        if not failed_any:
            self._note_event()  # _fail_request notes it otherwise
        return bool(self._active or self._queue)

    def _trace_fault(self, step: int, kind: str, site: str, lanes) -> None:
        """FaultInjector.on_fire callback: every chaos firing lands in the
        flight recorder as an instant at the moment it fires."""
        self.tracer.instant(
            "fault", kind=kind, site=site, lanes=list(lanes)
        )

    def _note_first_token(self, req: _PagedRequest) -> None:
        """First sampled token for this request (always the final prefill
        chunk of its first admission): stamp TTFT."""
        if req.first_token_at is None:
            req.first_token_at = time.perf_counter()
            ms = (req.first_token_at - req.submitted_at) * 1e3
            self.metrics.hist_ttft_ms.observe(ms)
            self.metrics.observe_class_latency("ttft", req.service_class, ms)
            self.tracer.mark("first_token", req.rid, step=self._step_index)

    def _note_terminal(self, req: _PagedRequest) -> None:
        """Terminal transition (finished or failed): stamp the end time and
        fold the request's mean inter-token latency into the TPOT
        histogram (needs >= 2 tokens to define an interval)."""
        if req.finished_at is not None:
            return
        req.finished_at = time.perf_counter()
        if req.first_token_at is not None and len(req.out) > 1:
            ms = (
                (req.finished_at - req.first_token_at) * 1e3
                / (len(req.out) - 1)
            )
            self.metrics.hist_tpot_ms.observe(ms)
            self.metrics.observe_class_latency("tpot", req.service_class, ms)
        self.metrics.note_class_event(
            req.service_class, "failed" if req.failed else "finished"
        )

    def _note_event(self) -> None:
        """Record one fault/pressure event for the degradation ladder."""
        self._last_event_step = self._step_index
        if self.paged.degrade_after_faults:
            self._event_steps.append(self._step_index)

    def _update_ladder(self) -> None:
        """Climb one rung when the event window saturates; step back down
        after a clean recovery window. A climb consumes its window (events
        re-accumulate before the next climb) and entering the top rung
        preempt-sheds the youngest lane — deliberate load shedding, so that
        preemption does not itself count as a pressure event."""
        cfg = self.paged
        if not cfg.degrade_after_faults:
            return
        horizon = self._step_index - cfg.degrade_window_steps
        while self._event_steps and self._event_steps[0] <= horizon:
            self._event_steps.popleft()
        if len(self._event_steps) >= cfg.degrade_after_faults:
            self._event_steps.clear()
            self._last_event_step = self._step_index
            if self._degrade_level < 4:
                self._degrade_level += 1
                self.metrics.degradations += 1
                self.metrics.degradation_level = self._degrade_level
                logger.warning(
                    "degradation ladder: climbing to level %d",
                    self._degrade_level,
                )
                self.tracer.instant(
                    "degradation", level=self._degrade_level,
                    direction="climb",
                )
            if self._degrade_level >= 4 and len(self._active) > 1:
                self._drain_pending()
                victim = max(self._active.values(), key=lambda r: r.rid)
                self._preempt(victim, shed=True)
        elif (
            self._degrade_level
            and self._step_index - self._last_event_step
            >= cfg.degrade_recover_steps
        ):
            self._degrade_level -= 1
            self.metrics.degradation_level = self._degrade_level
            # stagger further recovery: one rung per clean window
            self._last_event_step = self._step_index
            logger.info(
                "degradation ladder: recovered to level %d", self._degrade_level
            )
            self.tracer.instant(
                "degradation", level=self._degrade_level,
                direction="recover",
            )

    def _progress_sig(self) -> tuple:
        """Everything that moves when the engine does useful work; two
        consecutive equal signatures with work outstanding = a stalled
        step."""
        m = self.metrics
        return (
            m.admitted, m.finished, m.failed_requests, m.preemptions,
            m.prefill_chunks, m.prefill_tokens, len(self._queue),
            sum(len(r.out) for r in self._active.values()),
            sum(r.prefill_pos for r in self._active.values() if r.prefilling),
        )

    def _check_stall(self) -> None:
        limit = self.paged.stall_step_limit
        if not limit:
            return
        if not (self._active or self._queue):
            self._stall_steps = 0
            self._last_progress_sig = None
            return
        sig = self._progress_sig()
        if sig == self._last_progress_sig:
            self._stall_steps += 1
            if self._stall_steps >= limit:
                raise EngineStalledError(
                    limit,
                    {lane: r.rid for lane, r in self._active.items()},
                    [r.rid for r in self._queue],
                )
        else:
            self._stall_steps = 0
        self._last_progress_sig = sig

    def _audit(self, strict: bool = False):
        """Run the invariant auditor (serving/invariants.py); log + count
        violations, raising only in strict (debug) mode."""
        from neuronx_distributed_llama3_2_tpu.serving.invariants import (
            InvariantViolation,
            audit_engine,
        )

        violations = audit_engine(self)
        self._emit_action(
            ActionType.AUDIT, strict=strict, violations=len(violations),
        )
        if violations:
            self.metrics.audit_violations += len(violations)
            logger.error("serving invariant violations: %s", violations)
            from neuronx_distributed_llama3_2_tpu.serving.invariants import (
                summarize_violations,
            )

            self.tracer.instant(
                "invariant_violation", count=len(violations),
                detail=summarize_violations(violations),
            )
            if strict:
                raise InvariantViolation(violations)
        return violations

    def prewarm(self) -> None:
        """Compile the FULL declared catalog (``catalog.prewarm_keys()``)
        before any traffic, then :meth:`mark_steady` — no request ever
        pays a compile in its TTFT, and every later compile is a
        graftcheck GC008 finding. Dispatch arguments are aval twins of
        the real traffic arguments (every warmup call traces at exactly
        the shapes/dtypes traffic will dispatch at, so the jit trace
        cache holds ONE entry per program afterwards — the GC008
        re-lower check counts on that). Every dispatch
        writes only into the null block or rewrites current resident
        values, so token identity is untouched; plain ``jnp`` uploads
        keep the ``h2d_uploads`` choke-point counter at zero. The loop is
        the ``setup.prewarm`` span of the process's set-up record and each
        key a ``setup.program`` inside it: what JAX traces, lowers, compiles
        or loads for a key is booked to that key (utils/setup_record.py)."""
        eng = self.engine
        self._prewarming = True
        try:
            key = jax.random.key(0)
            zeros_b = jnp.zeros((eng.max_batch,), jnp.int32)
            table1 = jnp.asarray(self._prefill_table((), None))
            zero = jnp.asarray(0, jnp.int32)
            # fused-sampling trailing args (aval twins of traffic's):
            # decode/verify dispatch THE residents, prefill the (1,·)
            # per-admission sampling uploads. d_tail is a THUNK: the
            # lane_set arm donates and replaces the resident buffers, so
            # binding them once would hand pdecode/pverify deleted arrays.
            def d_tail() -> tuple:
                return (
                    (self._d_temps, self._d_topks, self._d_topps, self._d_rng)
                    if self._fused else (key,)
                )
            p_tail = (
                (
                    jnp.zeros((1, 2), jnp.uint32),
                    jnp.zeros((1,), jnp.float32),
                    jnp.zeros((1,), jnp.int32),
                    jnp.ones((1,), jnp.float32),
                )
                if self._fused else (key,)
            )
            with SETUP.span("setup.prewarm"):
                for key_ in self.catalog.prewarm_keys():
                    kind = key_[0]
                    with SETUP.span("setup.program", key=str(key_), kind=kind):
                        if kind == "copy_block":
                            # copy the null block onto itself: garbage -> garbage
                            self.cache = self._copy_block_fn(self.cache, zero, zero)
                        elif kind == "lane_set":
                            # rewrite lane 0's resident state with its current
                            # values (zeros + all-null table row; under fused
                            # sampling also the sentinel params + null key data)
                            fn = self._lane_set_program()
                            trow = jnp.full(
                                (self.table_width,), NULL_BLOCK, jnp.int32
                            )
                            if self._fused:
                                (
                                    self._d_tokens, self._d_positions,
                                    self._d_tables, self._d_temps, self._d_topks,
                                    self._d_topps, self._d_rng,
                                ) = fn(
                                    self._d_tokens, self._d_positions,
                                    self._d_tables, self._d_temps, self._d_topks,
                                    self._d_topps, self._d_rng,
                                    zero, zero, zero, trow,
                                    jnp.asarray(
                                        GREEDY_TEMPERATURE, jnp.float32
                                    ),
                                    zero, jnp.asarray(1.0, jnp.float32),
                                    jnp.zeros((2,), jnp.uint32),
                                )
                            else:
                                self._d_tokens, self._d_positions, self._d_tables = fn(
                                    self._d_tokens, self._d_positions, self._d_tables,
                                    zero, zero, zero, trow,
                                )
                        elif kind == "table_delta":
                            fn = self._table_delta_program()
                            self._d_tables = fn(
                                self._d_tables, zero, zero,
                                jnp.asarray(NULL_BLOCK, jnp.int32),
                            )
                        elif kind == "block_save":
                            # slice the null block out; the snapshot is discarded
                            self._block_save_fn(self.cache, zero)
                        elif kind == "block_restore":
                            # scatter an all-zeros payload into the null block at
                            # exactly traffic's upload shapes/dtypes
                            self.cache = self._block_restore_fn(
                                self.cache, zero, *self._null_block_payload()
                            )
                        elif kind == "pctx":
                            _, bucket, cfg, _g = key_
                            fn = self._prefill_ctx_program(bucket, cfg)
                            _, self.cache = fn(
                                eng.params, self.cache,
                                jnp.zeros((1, bucket), jnp.int32),
                                jnp.ones((1,), jnp.int32), table1, *p_tail,
                            )
                        elif kind == "psfx":
                            _, bucket, kv, cfg, _g = key_
                            fn = self._prefill_suffix_program(bucket, kv, cfg)
                            _, self.cache = fn(
                                eng.params, self.cache,
                                jnp.zeros((1, bucket), jnp.int32),
                                jnp.ones((1,), jnp.int32),
                                jnp.ones((1,), jnp.int32), table1, *p_tail,
                            )
                        elif kind == "pdecode":
                            _, cfg, kv, _g, _c = key_
                            fn = self._decode_program(cfg, kv)
                            # dispatch THE residents exactly like _step's decode
                            # (same committedness/sharding → same lowering) and
                            # reassign the donated outputs; every table row is
                            # still NULL, so the write lands in the null block and
                            # admission's lane_set rewrites the lane state anyway
                            args = (
                                eng.params, self.cache, self._d_tokens,
                                self._d_positions, self._d_tables, *d_tail(),
                            )
                            if self._check_logits:
                                toks, _, self._d_positions, self.cache = fn(
                                    *args, self._nan_mask((), "warmup")
                                )
                            else:
                                toks, self._d_positions, self.cache = fn(*args)
                            self._d_tokens = toks
                        elif kind == "pverify":
                            _, kv, k, _g, _c = key_
                            fn = self._verify_program(kv, k)
                            args = (
                                eng.params, self.cache, self._d_tokens,
                                self._d_positions, self._d_tables,
                                jnp.zeros((eng.max_batch, k), jnp.int32), zeros_b,
                                *(d_tail() if self._fused else ()),
                            )
                            if self._check_logits:
                                _, _, toks, self._d_positions, _, self.cache = fn(
                                    *args, self._nan_mask((), "warmup")
                                )
                            else:
                                _, _, toks, self._d_positions, self.cache = fn(*args)
                            self._d_tokens = toks
                        elif kind == "ptree":
                            _, kv, k, _g, _c = key_
                            fn = self._tree_program(kv, k)
                            # all-zero packed payload: zero live draft nodes per
                            # lane, so every lane is a plain decode row writing
                            # into the null block (the chain-degenerate tree)
                            args = (
                                eng.params, self.cache, self._d_tokens,
                                self._d_positions, self._d_tables,
                                jnp.zeros((eng.max_batch, 2 * k + 1), jnp.int32),
                                *(d_tail() if self._fused else ()),
                            )
                            if self._check_logits:
                                _, _, toks, self._d_positions, _, self.cache = fn(
                                    *args, self._nan_mask((), "warmup")
                                )
                            else:
                                _, _, toks, self._d_positions, self.cache = fn(*args)
                            self._d_tokens = toks
                        elif kind == "pmixed":
                            _, t, kv, _cfg, _g, _c = key_
                            fn = self._mixed_program(t, kv)
                            # all-zero row payload: every lane is a draft-len-0
                            # decode row, so the warmup is exactly a pdecode-shaped
                            # null-block write plus resident rewrite
                            args = (
                                eng.params, self.cache, self._d_tokens,
                                self._d_positions, self._d_tables,
                                jnp.zeros((eng.max_batch, t), jnp.int32),
                                zeros_b, zeros_b, zeros_b,
                                *(
                                    (jnp.zeros((eng.max_batch, t), jnp.int32),)
                                    if self._spec_tree else ()
                                ),
                                *(d_tail() if self._fused else ()),
                            )
                            if self._check_logits:
                                _, _, toks, self._d_positions, _, self.cache = fn(
                                    *args, self._nan_mask((), "warmup")
                                )
                            else:
                                _, _, toks, self._d_positions, self.cache = fn(*args)
                            self._d_tokens = toks
                        else:  # pragma: no cover - manifest/engine kind drift
                            raise ValueError(f"prewarm: unknown program kind {kind!r}")
            for warning in validate_ladder(self.model, self.catalog.ladder):
                logger.warning("catalog: %s", warning)
            logger.info(
                "prewarmed %d program(s): %s",
                self.metrics.prewarm_compiles, self.catalog.describe(),
            )
        finally:
            self._prewarming = False
        self.mark_steady()
        if self.paged.cost_accounting:
            # graftmeter: every catalog key just compiled — harvest the
            # device-cost ledger while the lowerings are trace-cache warm
            self.ensure_cost_profiles()
        if self.tracer.enabled:
            self.tracer.setup = self._setup_facts()

    # -- request lifecycle -------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        *,
        service_class: str = "batch",
        tenant: str = "default",
    ) -> int:
        if service_class not in SERVICE_CLASSES:
            raise ValueError(
                f"unknown service_class {service_class!r}; expected one of "
                f"{sorted(SERVICE_CLASSES)}"
            )
        if len(prompt) + self.gen.max_new_tokens > self.engine.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({self.gen.max_new_tokens}) exceeds cache capacity "
                f"({self.engine.max_seq_len})"
            )
        bs = self.paged.block_size
        worst = (
            _ceil_div(len(prompt) + self.gen.max_new_tokens, bs)
            + self.paged.decode_reserve_blocks
        )
        if worst > self.allocator.usable_blocks:
            raise ValueError(
                f"request needs up to {worst} KV blocks but the pool has "
                f"{self.allocator.usable_blocks} usable blocks — raise "
                f"PagedConfig.num_blocks or shrink max_new_tokens"
            )
        rid = self._next_rid
        self._next_rid += 1
        req = _PagedRequest(
            rid=rid, prompt=list(prompt), out=[],
            submitted_at=time.perf_counter(),
            service_class=service_class, tenant=tenant,
            submitted_step=self._step_index,
        )
        self._queue.append(req)
        self._requests[rid] = req
        self.metrics.submitted += 1
        self.metrics.note_class_event(service_class, "submitted")
        self.metrics.queued_requests = len(self._queue)
        self.tracer.request_state(rid, "queued")
        return rid

    def cancel(self, rid: int, reason: str = "cancelled by client") -> bool:
        """Client-initiated terminal cancel (graftserve front door).

        Routes through the existing failure domain: drain any in-flight
        lookahead (``_fail_request`` is only legal pipeline-drained), then
        fail the request with ``error=reason`` — blocks released, lane
        freed and mirrors nulled through ``_release_lane``, FINISH
        (failed=True) emitted for the action trace, terminal timing
        stamped. Queued, prefilling, and decoding requests all take the
        same path; survivors' resident state is untouched, so their token
        streams are unchanged (cancellation-parity tests pin this).

        Returns True if the request transitioned to terminal now, False
        if it was already done. Raises KeyError for an unknown rid. Must
        be called between steps (same threading contract as submit)."""
        req = self._requests.get(rid)
        if req is None:
            raise KeyError(f"unknown request id {rid}")
        if req.done:
            return False
        self._drain_pending()
        self._fail_request(req, reason)
        self.metrics.cancelled_requests += 1
        self.metrics.queued_requests = len(self._queue)
        return True

    # -- graftplan: workload export + policy-table load --------------------

    def export_workload(self) -> Any:
        """Serialize this engine's geometry and every submitted request
        span as a :class:`~..analysis.graftplan.Workload` — the recorded
        trace the graftplan simulator replays and the autotuner searches
        over. Plain data (no arrays, no engine handles); call after the
        run so the action-trace summary covers it."""
        from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
            Workload,
            WorkloadRequest,
        )
        from neuronx_distributed_llama3_2_tpu.serving.accounting import (
            EngineDims,
        )

        requests = [
            WorkloadRequest(
                rid=r.rid,
                prompt_tokens=len(r.prompt),
                max_new_tokens=self.gen.max_new_tokens,
                service_class=r.service_class,
                tenant=r.tenant,
                submitted_step=r.submitted_step,
            )
            for r in sorted(self._requests.values(), key=lambda r: r.rid)
        ]
        trace = {
            "steps": len(self.action_trace),
            "actions": sum(
                len(acts) for _, _, acts in self.action_trace
            ),
            "host_schedule_ms": self.metrics.host_schedule_ms,
        }
        return Workload(
            block_size=self.paged.block_size,
            num_blocks=self.paged.num_blocks,
            decode_reserve_blocks=self.paged.decode_reserve_blocks,
            lanes=self.engine.max_batch,
            max_seq_len=self.engine.max_seq_len,
            prefill_chunk_tokens=self.paged.prefill_chunk_tokens,
            prefill_buckets=tuple(self._prefill_buckets),
            kv_buckets=tuple(self._kv_buckets),
            dims=EngineDims.from_engine(self),
            requests=requests,
            slo_ttft_p99_ms=self.paged.slo_ttft_p99_ms,
            slo_tpot_p99_ms=self.paged.slo_tpot_p99_ms,
            trace=trace,
        )

    def load_policy_table(self, source: Any, strict: bool = True) -> list:
        """Install a graftplan policy table (path or parsed dict) on the
        live step policy under GC011: certificate present and explorer-
        clean, automaton fingerprint fresh, ladder fingerprint fresh
        against *this* engine's completed ladders, budgets on-ladder.
        ``strict`` (the default, and the ``policy_table_path`` route)
        raises :class:`~..analysis.graftplan.PolicyTableError` on any
        finding; ``strict=False`` installs anyway and flips the
        ``policy_table_stale`` gauge (certification harness / expert
        seam). Returns the findings list."""
        import json as _json

        from neuronx_distributed_llama3_2_tpu.analysis.graftplan import (
            PolicyTableError,
            check_policy_table,
        )

        if isinstance(source, (str, bytes)):
            with open(source) as fh:
                table = _json.load(fh)
        else:
            table = dict(source)
        findings = check_policy_table(
            table,
            prefill_buckets=self._prefill_buckets,
            kv_buckets=self._kv_buckets,
        )
        if findings and strict:
            raise PolicyTableError(findings)
        apply = getattr(self.policy, "apply", None)
        if apply is None:
            raise ValueError(
                f"step policy {type(self.policy).__name__} cannot load a "
                'policy table; construct the engine with '
                'PagedConfig(step_policy="table")'
            )
        apply(table)
        self.metrics.policy_table_id = str(table.get("table_id", ""))[:12]
        self.metrics.policy_table_stale = 1 if findings else 0
        burn = (table.get("objective") or {}).get(
            "simulated_burn_by_class"
        ) or {}
        self.metrics.policy_simulated_burn = {
            str(cls): dict(v) for cls, v in burn.items()
        }
        return findings

    def _reorder_queue(self, order: Sequence[int]) -> None:
        """Reorder the waiting queue to match ``order`` (a ranking of rids
        from a policy's ADMIT ``admit_order`` meta). Rids absent from the
        queue are ignored (finished/cancelled since the policy read its
        view); queued requests absent from ``order`` keep their relative
        FCFS order behind the ranked ones — a policy can promote without
        being able to lose requests."""
        by_rid = {r.rid: r for r in self._queue}
        ranked = [by_rid.pop(rid) for rid in order if rid in by_rid]
        self._queue = ranked + [r for r in self._queue if r.rid in by_rid]

    def _admit(self) -> None:
        """Admission wave, wrapped in one flight-recorder slice when there
        is anything to admit (the traced span covers every prefill the
        wave runs inline)."""
        if not (self._queue and self._free_lanes):
            return
        lanes_before = set(self._active)
        tr = self.tracer
        try:
            if not tr.enabled:
                self._admit_wave()
            else:
                before = self.metrics.admitted
                t0 = tr.now()
                try:
                    self._admit_wave()
                finally:
                    tr.complete(
                        "admit", t0, waiting=len(self._queue),
                        admitted=self.metrics.admitted - before,
                    )
        finally:
            # a lane admitted-and-finished inside the wave is absent here;
            # its FINISH record (already emitted) carries the lane id
            self._emit_action(
                ActionType.ADMIT,
                lanes=sorted(set(self._active) - lanes_before),
                waiting=len(self._queue),
            )

    # -- tiered KV storage (docs/serving.md "Tiered KV storage") -----------

    def _null_block_payload(self) -> tuple:
        """Aval twins of a restore's uploaded payload arrays (one block's
        slice of every cache array: k/v, plus scale tiles when quantized): plain ``jnp`` zeros,
        so prewarm's ``block_restore`` dispatch traces at exactly traffic's
        shapes/dtypes without touching the ``h2d_uploads`` counter."""
        return tuple(
            jnp.zeros(a.shape[:1] + a.shape[2:], a.dtype)
            for a in jax.tree.leaves(self.cache)
        )

    def _spill_block(self, bid: int) -> bool:
        """``BlockAllocator.spill_hook``: move the eviction victim's
        payload toward host RAM instead of discarding it. The block_save
        program slices a fresh snapshot out of the pool (pure read, not
        donated — the buffers stay valid after the allocator reuses the
        id; dispatched in stream order, so any in-flight decode writes are
        already reflected), the radix node flips to its spilled residency
        state, and the snapshot joins the bounded background drain queue —
        the blocking D2H copy happens at drain time, off the dispatch
        path. The bid rides as a plain control scalar (the copy_block
        precedent), not a counted upload. False = no index node to retain;
        the allocator falls through to the normal discard path."""
        if self._block_save_fn is None or bid not in self.index._by_block:
            return False
        out = self._block_save_fn(self.cache, jnp.asarray(bid, jnp.int32))
        nbytes = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a in out
        )
        sid = self.host_tier.allocate_sid()
        self.index.mark_spilled(bid, sid)
        self._spill_pending.append((sid, out, nbytes))
        self.metrics.blocks_spilled += 1
        # bounded queue: past the depth, the oldest snapshot drains early
        while len(self._spill_pending) > self.paged.spill_queue_depth:
            self._drain_one_spill()
        return True

    def _drain_one_spill(self) -> None:
        sid, out, nbytes = self._spill_pending.popleft()
        if sid not in self.index._spilled:
            return  # node dropped while the snapshot waited; forget it
        payload = tuple(np.asarray(a) for a in out)  # blocking D2H copy
        self.host_tier.put_at(sid, payload, nbytes)
        self.metrics.spill_bytes += nbytes

    def _drain_spills(self) -> None:
        """Commit every enqueued spill snapshot to the host tier. Called
        at the end of :meth:`step` (the background drain — device work for
        the step is already in flight, so the D2H wait overlaps it) and
        before a restore prices a spilled run."""
        if not self._spill_pending:
            return
        t0 = time.perf_counter()
        n = len(self._spill_pending)
        while self._spill_pending:
            self._drain_one_spill()
        if self.tracer.enabled:
            self.tracer.complete(
                "spill_drain", t0, time.perf_counter(), blocks=n
            )

    def _drop_spill_payload(self, sid: int) -> None:
        """``RadixPrefixIndex.on_spill_drop``: forget a spilled payload in
        both places it can live — the host tier and the not-yet-drained
        snapshot queue."""
        if self.host_tier is not None:
            self.host_tier.drop(sid)
        if self._spill_pending:
            self._spill_pending = deque(
                e for e in self._spill_pending if e[0] != sid
            )

    def _restore_price(self, n_bytes: int, gain: int) -> Tuple[float, float]:
        """``(restore_seconds, recompute_seconds)`` for a spilled run:
        payload bytes over the PCIe-class host link vs prefill FLOPs at
        the padded rung, once a chunk where the engine chunks (``gain /
        chunk`` dispatches of the chunk's rung: the ladder holds no rung
        for a whole run) — from the harvested CostProfiles when graftmeter
        ran (``PagedConfig.cost_accounting``), the same analytic formulas
        otherwise."""
        from neuronx_distributed_llama3_2_tpu.serving.accounting import (
            HOST_LINK_BW_BYTES_PER_S,
            EngineDims,
            analytic_cost,
        )

        restore_s = n_bytes / HOST_LINK_BW_BYTES_PER_S
        # a chunked engine recomputes the run a chunk a dispatch
        piece = max(min(gain, self.paged.prefill_chunk_tokens or gain), 1)
        pieces = max(_ceil_div(gain, piece), 1)
        bucket = pick_bucket(self._prefill_buckets, piece)
        flops = None
        if self.cost_profiles:
            for k, p in self.cost_profiles.items():
                if k[0] == "pctx" and int(k[1]) == bucket:
                    flops = p.flops
                    break
        if flops is None:
            if self._restore_dims is None:
                self._restore_dims = EngineDims.from_engine(self)
            flops = analytic_cost(("pctx", bucket), self._restore_dims)[0]
        peak = self.metrics.peak_flops_per_chip * max(
            self.metrics.tp_size, 1
        )
        return restore_s, pieces * flops / max(peak, 1.0)

    def _maybe_restore(
        self, seq: List[int], matched: int, mblocks: List[int]
    ) -> Tuple[int, List[int]]:
        """Restore-over-recompute at admission: when the radix walk
        extends past the resident prefix into spilled nodes, price the
        spilled run and — when restoring wins — upload the payloads
        through the metered ``_upload`` funnel into freshly allocated
        blocks, heal the nodes back to resident, and hand the extended
        match to the admission. Restores ride admission (where prefill
        uploads already live), never the steady-state dispatch path. An
        injected host-tier fault (or a payload lost to the tier's budget)
        drops the spilled run inside its own failure domain and falls
        back to re-prefilling; resident survivors are untouched."""
        ext_matched, chain = self.index.walk(seq)
        spilled = [n for n in chain if n.block == SPILLED_BLOCK]
        gain = ext_matched - matched
        if not spilled or gain <= 0:
            return matched, mblocks
        self._drain_spills()  # payloads must be host-resident to price
        if self.injector is not None and self.injector.host_tier_fault():
            # corrupt/evict the victim before restore: the shallowest
            # spilled node's subtree (the whole spilled run) is the
            # failure domain — drop it and re-prefill
            self.index.invalidate_spilled(spilled[0].sid)
            self.metrics.restore_fallbacks += 1
            return matched, mblocks
        payloads = []
        for node in spilled:
            p = self.host_tier.get(node.sid)
            if p is None:
                # budget eviction raced the walk; nothing to restore from
                self.metrics.restore_fallbacks += 1
                return matched, mblocks
            payloads.append(p)
        total_bytes = sum(a.nbytes for p in payloads for a in p)
        restore_s, recompute_s = self._restore_price(total_bytes, gain)
        xo = self.paged.restore_crossover
        alloc = self.allocator
        if (
            xo <= 0
            or restore_s > xo * recompute_s
            or alloc.available() < len(spilled) + 1
        ):
            self.metrics.restore_declined += 1
            return matched, mblocks
        t0 = time.perf_counter()
        # hold the chain's resident blocks so our own allocations cannot
        # evict them mid-restore; restored blocks join the held list and
        # everything is released (-> parked cached) once the chain heals
        held: List[int] = []
        for node in chain:
            if node.block >= 0:
                alloc.incref(node.block)
                held.append(node.block)
        ok = True
        n_restored = 0
        for node, payload in zip(spilled, payloads):
            if node.sid not in self.index._spilled:
                ok = False
                break
            nb = alloc.alloc()
            if nb is None:
                ok = False
                break
            args = tuple(self._upload(a, a.dtype) for a in payload)
            self.metrics.restore_uploads += len(args)
            self.cache = self._block_restore_fn(
                self.cache, jnp.asarray(nb, jnp.int32), *args
            )
            self.index.heal(node, nb)  # drops the host payload too
            held.append(nb)
            n_restored += 1
        for b in held:
            alloc.release(b)
        if not ok:
            self.metrics.restore_fallbacks += 1
            return matched, mblocks
        self.metrics.blocks_restored += n_restored
        self.metrics.restore_hits += 1
        self.metrics.restore_bytes += total_bytes
        self.index.hit_tokens += gain  # restored tokens ARE prefix hits
        if self.tracer.enabled:
            self.tracer.complete(
                "restore", t0, time.perf_counter(),
                blocks=n_restored, bytes=total_bytes, tokens=gain,
            )
        self._emit_action(
            ActionType.RESTORE, lanes=[], blocks=n_restored, tokens=gain,
        )
        return ext_matched, [n.block for n in chain]

    def _admit_wave(self) -> None:
        bs = self.paged.block_size
        alloc = self.allocator
        while self._queue and self._free_lanes:
            req = self._queue[0]
            seq = req.prompt + req.out  # resume re-prefills generated tokens
            if self._share_prefixes:
                matched, mblocks = self.index.match(seq)
                if self._spill and self.index.num_spilled:
                    # tiered KV: the walk may extend past the resident
                    # prefix into spilled nodes — restore them H2D when
                    # the cost model says the bytes beat re-prefilling
                    matched, mblocks = self._maybe_restore(
                        seq, matched, mblocks
                    )
            else:
                matched, mblocks = 0, []
            # always leave >= 1 token to prefill: the admission forward must
            # produce the logits at the last position
            cached = min(matched, len(seq) - 1)
            n_total = _ceil_div(len(seq), bs)
            n_shared_full = cached // bs
            need_new = (n_total - n_shared_full) + self.paged.decode_reserve_blocks
            if alloc.available() < need_new:
                self.metrics.admit_blocked += 1
                return  # FCFS head-of-line: wait for blocks to drain
            self._queue.pop(0)
            # take shared refs BEFORE allocating, so our own allocations
            # cannot evict the blocks we are about to use
            table = list(mblocks[: _ceil_div(cached, bs)])
            for b in table:
                alloc.incref(b)
            ok = True
            if cached % bs:
                # partially shared last block: the suffix's first write lands
                # inside it -> move onto a private copy now
                src = table[-1]
                wb, copied = alloc.copy_on_write(src)
                if wb is None:
                    ok = False
                else:
                    if copied:
                        self.cache = self._copy_block_fn(
                            self.cache,
                            jnp.asarray(src, jnp.int32),
                            jnp.asarray(wb, jnp.int32),
                        )
                    table[-1] = wb
            while ok and len(table) < n_total:
                nb = alloc.alloc()
                if nb is None:
                    ok = False
                else:
                    table.append(nb)
            if not ok:
                # lost the budget race (should not happen: available() was
                # checked); back off cleanly and retry next step
                for b in table:
                    alloc.release(b)
                self._queue.insert(0, req)
                return
            lane = self._free_lanes.pop(0)
            req.lane = lane
            req.table = table
            req.cached_tokens += cached
            self._tables[lane, :] = NULL_BLOCK
            self._active[lane] = req
            # fused sampling: (re-)install the lane's params + base key
            # before any prefill of this admission can draw from them
            self._install_lane_sampling(lane, req)
            self.metrics.admitted += 1
            self.metrics.cached_tokens += cached
            if req.admitted_at is None:  # queue_ms = first admission wait
                req.admitted_at = time.perf_counter()
            self.tracer.request_state(req.rid, "prefilling")
            chunk = self.paged.prefill_chunk_tokens
            if (chunk and len(seq) - cached > chunk) or (
                self._fused_step and cached > 0
            ):
                # chunked admission: the lane holds its blocks but joins the
                # decode batch only after the final chunk. Until then the
                # decode-visible table row stays all-null — the batched
                # decode program scatter-writes K/V for EVERY lane, and a
                # live table would let those garbage writes land in this
                # request's real blocks mid-prefill. Prefix registration is
                # deferred too: the blocks hold valid tokens only when the
                # last chunk completes.
                #
                # Fused mixed-mode step: EVERY cached-prefix admission walks
                # this route (the psfx program kind is never dispatched) and
                # the full allocated table goes live immediately — the
                # pmixed program reads and writes the chunk rows through the
                # decode-visible row. Safe under the overwrite-frontier
                # invariant: a garbage row the batched program writes is
                # always rewritten by the dispatch that first admits it into
                # a mask, and rows past the allocation land in the null
                # block.
                req.prefilling = True
                req.prefill_pos = cached
                req.prefill_target = len(seq)
                self._tokens[lane] = 0
                self._positions[lane] = 0
                if self._fused_step:
                    self._tables[lane, : len(table)] = table
                    # park the resident write row PAST the prompt: row 0 of
                    # a live table can be a *shared* prefix block, and any
                    # batched program writes garbage at every lane's
                    # resident row — prefill_target's row is private (or
                    # null past the allocation) and decode overwrites it
                    # before any mask admits it
                    self._positions[lane] = req.prefill_target
                self._dirty_lanes.add(lane)
                continue
            suffix = seq[cached:]
            k = None
            if not self._fused:
                self._key, k = jax.random.split(self._key)
            t_p = time.perf_counter()
            try:
                self._chaos_device("prefill", (lane,))
                first = int(self._read_tokens(
                    self._prefill(suffix, cached, table, k, lane=lane)
                )[0])
            except InjectedFault as fault:
                # admission prefill fault: only this request dies — its
                # lane/table teardown leaves the admission wave consistent
                self._fail_request(req, str(fault))
                continue
            t_p1 = time.perf_counter()
            req.prefill_ms += (t_p1 - t_p) * 1e3
            if self.tracer.enabled:
                self.tracer.complete(
                    "prefill", t_p, t_p1, rid=req.rid,
                    tokens=len(suffix), cached=cached,
                    bucket=self._last_prefill_bucket,
                    kv_bucket=self._last_prefill_kv,
                    pad=self._last_prefill_bucket - max(len(suffix), 1),
                    **self._last_prefill_tiles,
                )
            req.out.append(first)
            req.position = len(seq)
            self._note_first_token(req)
            self.tracer.request_state(req.rid, "active")
            self._tokens[lane] = first
            self._positions[lane] = req.position
            self._tables[lane, : len(table)] = table
            self._dirty_lanes.add(lane)
            self.metrics.prefill_tokens += len(suffix)
            if self._share_prefixes:
                # register the prompt's full blocks immediately so requests
                # admitted later in this same wave share them; the partial
                # tail block stays private (decode writes into it)
                n_full = len(seq) // bs
                if n_full:
                    self.index.insert(seq[: n_full * bs], table[:n_full])
            self._maybe_finish(req)

    def _prefill(
        self, suffix: List[int], cached: int, table: List[int], key,
        table_dev=None, lane: Optional[int] = None,
    ):
        """Dispatch one (whole or chunk) prefill and return its sampled token
        as the device array it is: the caller reads it back where the token
        is a request's first, and never for a non-final chunk's, which
        nobody uses. ``table_dev`` short-circuits the per-call block-table
        upload — chunked prefill passes the same (1, W) device array for
        every chunk of an admission instead of re-uploading it each time.
        Under fused sampling ``key`` is None and ``lane`` selects the
        installed sampling mirrors that ride in as the (1,·) trailing
        uploads."""
        eng = self.engine
        bucket = pick_bucket(self._prefill_buckets, max(len(suffix), 1))
        self._last_prefill_bucket = bucket  # tracer pad-waste tag
        self._last_prefill_kv = 0
        ids = np.zeros((1, bucket), np.int32)
        ids[0, : len(suffix)] = suffix
        length = np.asarray([max(len(suffix), 1)], np.int32)
        if table_dev is None:
            table_dev = self._upload(self._prefill_table(table, lane))
        tail = self._lane_sampling_args(lane) if self._fused else (key,)
        if cached == 0:
            if self._has_state:
                self.metrics.state_resets += 1   # this pctx begins a state from zero
            fn = self._prefill_ctx_program(bucket, self._decode_cfg())
            tok, self.cache = fn(
                eng.params, self.cache, self._upload(ids),
                self._upload(length), table_dev, *tail,
            )
        else:
            kv_limit = self._kv_bucket(min(cached + bucket, eng.max_seq_len))
            self._last_prefill_kv = kv_limit
            fn = self._prefill_suffix_program(
                bucket, kv_limit, self._decode_cfg()
            )
            tok, self.cache = fn(
                eng.params, self.cache, self._upload(ids),
                self._upload(np.asarray([cached], np.int32)),
                self._upload(length), table_dev, *tail,
            )
        if self._chunk_tiles:
            kernel, walked, rung = self.model.chunk_tiles(
                bucket, cached, self._last_prefill_kv or None)
            self.metrics.sparse_kernel_chunks += int(kernel)
            self._last_prefill_tiles = {"sparse_tiles_walked": walked, "sparse_tiles_rung": rung}
        # graftmeter pad-waste fold: every prefill (admission or chunk)
        # funnels through here with `fn` bound to the dispatched program
        self.metrics.note_prefill_dispatch(
            bucket, max(len(suffix), 1),
            *(self._flops_by_key.get(fn.key) or (0.0, 0.0)),
        )
        return tok

    def _advance_prefills(self, budget_tokens: Optional[int] = None) -> None:
        """One fixed-budget chunk per prefilling lane per step (Sarathi-Serve
        chunked prefill): each chunk runs through the existing suffix-prefill
        program starting at ``prefill_pos``, so all non-final chunks of a
        given chunk size reuse ONE compiled (bucket, kv_limit) family. A
        non-final chunk's sampled token is never read back — only the final
        chunk's logits are the real next-token distribution — and bucket
        padding is safe for the same reason it always was: padded writes
        land at rows a later chunk overwrites before any mask admits them.

        ``budget_tokens`` (graftserve, via PREFILL_CHUNK action meta) caps
        the *aggregate* prefill tokens this wave dispatches: once at least
        one chunk ran and the budget is spent, remaining prefilling lanes
        wait for the next step. At least one lane always advances when any
        lane is prefilling — a budget can pace prefill, never starve it.
        ``None`` (the default, and the only value FIFO ever passes) is the
        historical unbounded wave, byte-for-byte."""
        chunk = self.paged.prefill_chunk_tokens
        bs = self.paged.block_size
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
            seq = req.prompt + req.out
            start = req.prefill_pos
            piece = seq[start: start + chunk]
            final = start + len(piece) >= req.prefill_target
            k = None
            if not self._fused:
                self._key, k = jax.random.split(self._key)
            if req.table_dev is None:
                # one upload for the whole chunk walk: the admission
                # allocated the full table, so every chunk sees the same row
                req.table_dev = self._upload(self._prefill_table(req.table, lane))
            t_p = time.perf_counter()
            try:
                self._chaos_device("prefill", (lane,))
                tok = self._prefill(
                    piece, start, req.table, k, req.table_dev, lane=lane
                )
                if final:
                    # the one token of a chunk walk anybody uses; the step
                    # goes on to its decode dispatch behind the others
                    tok = int(self._read_tokens(tok)[0])
            except InjectedFault as fault:
                # chunk fault: this lane's prefill walk dies, the other
                # prefilling/decoding lanes are untouched
                self._fail_request(req, str(fault))
                continue
            t_p1 = time.perf_counter()
            req.prefill_ms += (t_p1 - t_p) * 1e3
            if self.tracer.enabled:
                self.tracer.complete(
                    "prefill_chunk", t_p, t_p1, rid=req.rid,
                    tokens=len(piece), final=final,
                    bucket=self._last_prefill_bucket,
                    kv_bucket=self._last_prefill_kv,
                    pad=self._last_prefill_bucket - max(len(piece), 1),
                    **self._last_prefill_tiles,
                )
            req.prefill_pos = start + len(piece)
            spent += len(piece)
            self.metrics.prefill_tokens += len(piece)
            self.metrics.prefill_chunks += 1
            self._emit_action(
                ActionType.PREFILL_CHUNK, rid=req.rid, lane=lane,
                tokens=len(piece), final=final,
            )
            if not final:
                continue
            # final chunk: sample the first token, install the real table
            # into the decode batch, register the prompt for prefix sharing
            req.prefilling = False
            req.table_dev = None
            req.out.append(tok)
            req.position = req.prefill_target
            self._note_first_token(req)
            self.tracer.request_state(req.rid, "active")
            self._tokens[lane] = tok
            self._positions[lane] = req.position
            self._tables[lane, : len(req.table)] = req.table
            self._dirty_lanes.add(lane)
            if self._share_prefixes:
                n_full = len(seq) // bs
                if n_full:
                    self.index.insert(seq[: n_full * bs], req.table[:n_full])
            self._maybe_finish(req)

    def _preempt(self, req: _PagedRequest, shed: bool = False) -> None:
        """Pool exhausted: bump the request back to the queue head. Its
        registered prefix blocks park in the cached LRU, so re-admission
        usually re-shares them instead of re-prefilling from scratch.
        A pool-pressure preemption counts as a degradation-ladder event;
        the ladder's own top-rung load shedding (``shed=True``) does not —
        deliberate shedding must not retrigger the ladder."""
        lane = req.lane
        self._release_lane(req)
        req.position = 0
        # a victim caught mid-chunked-prefill restarts its prefill from the
        # (possibly re-matched) cached prefix on re-admission
        req.prefilling = False
        req.prefill_pos = 0
        req.prefill_target = 0
        self._queue.insert(0, req)
        req.preemptions += 1
        self.metrics.preemptions += 1
        self._emit_action(
            ActionType.PREEMPT, rid=req.rid, lane=lane, shed=shed,
        )
        self.tracer.instant("preempt", rid=req.rid, shed=shed)
        self.tracer.request_state(req.rid, "preempted")
        if not shed:
            self._note_event()  # sustained pool pressure feeds the ladder
        logger.debug(
            "preempted request %d (pool exhausted): %d generated so far",
            req.rid, len(req.out),
        )
        if self.paged.audit_debug:
            self._audit(strict=True)

    def _ensure_decode_blocks(self) -> None:
        """Every active lane's next write row must be backed by a real
        block; allocate on block boundaries, preempting the youngest active
        request when the pool (free + evictable) runs dry. The write row is
        the *dispatch frontier* (``self._positions`` mirror) — equal to
        ``req.position`` in the sync loop, one ahead of it while a
        lookahead step is in flight."""
        bs = self.paged.block_size
        for lane in sorted(self._active, key=lambda l: self._active[l].rid):
            req = self._active.get(lane)
            if req is None:
                continue  # preempted while servicing an older lane
            if req.prefilling:
                continue  # admission already allocated the whole-prompt table
            if int(self._positions[lane]) // bs < len(req.table):
                continue
            while True:
                nb = self.allocator.alloc()
                if nb is not None:
                    self._append_block(lane, req, nb)
                    break
                victim = max(self._active.values(), key=lambda r: r.rid)
                self._preempt(victim)
                if victim is req:
                    break  # preempted ourselves; nothing left to back

    def _append_block(self, lane: int, req: _PagedRequest, nb: int) -> None:
        req.table.append(nb)
        col = len(req.table) - 1
        self._tables[lane, col] = nb
        self._table_delta_list.append((lane, col, nb))

    def _ensure_decode_blocks_async(self) -> bool:
        """Non-preempting variant for the async dispatch path: back every
        decode lane's next write row from the pool (eviction of cached LRU
        blocks is fine — pure host bookkeeping), but if an allocation would
        require preempting an *active* lane, report False so the step drops
        to the synchronous loop, which drains the in-flight step first and
        then preempts with a consistent view."""
        bs = self.paged.block_size
        for lane in sorted(self._active, key=lambda l: self._active[l].rid):
            req = self._active[lane]
            if req.prefilling:
                continue
            if int(self._positions[lane]) // bs < len(req.table):
                continue
            nb = self.allocator.alloc()
            if nb is None:
                return False  # pool dry: preemption needed → sync fallback
            self._append_block(lane, req, nb)
        return True

    def _finish_due(self, req: _PagedRequest) -> bool:
        eos = self.gen.eos_token_id
        return (
            req.done
            or (eos is not None and bool(req.out) and req.out[-1] == eos)
            or len(req.out) >= self.gen.max_new_tokens
        )

    def _maybe_finish(self, req: _PagedRequest) -> None:
        if not self._finish_due(req) or req.rid in self._finished:
            return
        req.done = True
        bs = self.paged.block_size
        if self._share_prefixes and req.table:
            # cache the whole materialized sequence (prompt + generated):
            # rows [0, position) are valid — the final token's KV was never
            # written, so it is excluded
            seq = (req.prompt + req.out)[: req.position]
            self.index.insert(seq, req.table[: _ceil_div(req.position, bs)])
        lane = req.lane
        if req.lane is not None:
            self._release_lane(req)
        self._emit_action(
            ActionType.FINISH, rid=req.rid, lane=lane, failed=False,
        )
        self._finished[req.rid] = req
        self.metrics.finished += 1
        self._note_terminal(req)
        self.tracer.request_state(req.rid, "finished")
        if self.paged.audit_debug:
            self._audit(strict=True)

    # -- serving loop -------------------------------------------------------

    def _flush_state(self) -> None:
        """Push queued host-side lane mutations into the device-resident
        arrays. Single-entry table deltas (block growth) donate only the
        tables array, so they are safe to issue while a lookahead step is
        in flight; full-lane syncs donate all three residents and may only
        run with no step pending (dirty lanes are only ever marked by
        scheduler events, which drain the pipeline first)."""
        if self._table_delta_list:
            self._emit_action(
                ActionType.TABLE_DELTA_FLUSH,
                n=len(self._table_delta_list),
                in_flight=self._pending is not None,
            )
            with self.tracer.phase(
                "table_delta_flush", n=len(self._table_delta_list)
            ):
                fn = self._table_delta_program()
                for lane, col, val in self._table_delta_list:
                    if lane in self._dirty_lanes:
                        continue  # full-lane sync below rewrites the whole row
                    self._d_tables = fn(
                        self._d_tables,
                        self._upload(lane), self._upload(col), self._upload(val),
                    )
                    self.metrics.table_deltas += 1
                self._table_delta_list.clear()
        if self._dirty_lanes:
            assert self._pending is None, "full-lane sync with step in flight"
            self._emit_action(
                ActionType.LANE_SET_FLUSH,
                lanes=sorted(self._dirty_lanes),
                in_flight=self._pending is not None,
            )
            with self.tracer.phase(
                "lane_sync_flush", lanes=sorted(self._dirty_lanes)
            ):
                fn = self._lane_set_program()
                for lane in sorted(self._dirty_lanes):
                    if self._fused:
                        (
                            self._d_tokens, self._d_positions,
                            self._d_tables, self._d_temps, self._d_topks,
                            self._d_topps, self._d_rng,
                        ) = fn(
                            self._d_tokens, self._d_positions,
                            self._d_tables, self._d_temps, self._d_topks,
                            self._d_topps, self._d_rng,
                            self._upload(lane),
                            self._upload(self._tokens[lane]),
                            self._upload(self._positions[lane]),
                            self._upload(self._tables[lane]),
                            self._upload(self._temps[lane], jnp.float32),
                            self._upload(self._topks[lane]),
                            self._upload(self._topps[lane], jnp.float32),
                            self._upload(self._rng[lane], jnp.uint32),
                        )
                    else:
                        self._d_tokens, self._d_positions, self._d_tables = fn(
                            self._d_tokens, self._d_positions, self._d_tables,
                            self._upload(lane),
                            self._upload(self._tokens[lane]),
                            self._upload(self._positions[lane]),
                            self._upload(self._tables[lane]),
                        )
                    self.metrics.lane_syncs += 1
                self._dirty_lanes.clear()

    def _read_and_apply(self, pending: tuple) -> None:
        """Read one dispatched step's sampled tokens and advance request
        state. If a lane finished, the in-flight lookahead step (if any) is
        its lame-duck step: drain it too, apply its tokens to the surviving
        lanes (for them it is an ordinary decode step), discard the finished
        lanes' post-EOS tokens, and only then release the finished lanes'
        blocks — device program order guarantees the lame-duck KV writes
        landed before any later program can touch the recycled blocks.

        A lane whose checked dispatch reported non-finite logits commits
        nothing (its sampled token is garbage) and is quarantined exactly
        like a finishing lane: the in-flight lookahead — which dispatched
        from the garbage resident token — drains as *its* lame-duck step
        and the lane's request fails terminally."""
        toks, lanes, idx, finite = pending
        arr = self._read_tokens(toks)
        fin = None if finite is None else self._read_tokens(finite)
        self._last_readback_lag = self._dispatch_count - idx
        eng = self.engine
        finishing: List[_PagedRequest] = []
        quarantined: List[_PagedRequest] = []
        for lane in lanes:
            req = self._active.get(lane)
            if req is None:
                continue  # lane torn down between dispatch and readback
            if fin is not None and not bool(fin[lane]):
                quarantined.append(req)
                continue
            req.out.append(int(arr[lane]))
            req.position += 1
            self._tokens[lane] = arr[lane]
            if req.position >= eng.max_seq_len - 1:
                req.done = True
            if self._finish_due(req):
                finishing.append(req)
        # emitted AFTER the commit loop: at emission the host request state
        # is consistent again, so the explorer's per-action audit hook sees
        # no transient frontier lag
        self._emit_action(
            ActionType.READBACK, lanes=list(lanes),
            lag=self._last_readback_lag,
        )
        if (finishing or quarantined) and self._pending is not None:
            # Lame-duck drain: the lookahead step already ran with the
            # finished (or quarantined) lanes still in the batch.
            toks2, lanes2, idx2, finite2 = self._pending
            self._pending = None
            arr2 = self._read_tokens(toks2)
            fin2 = None if finite2 is None else self._read_tokens(finite2)
            self._last_readback_lag = self._dispatch_count - idx2
            dead = {r.lane for r in finishing} | {r.lane for r in quarantined}
            for lane in lanes2:
                if lane in dead:
                    self.metrics.lame_duck_tokens += 1
                    # the discarded dispatch advanced the frontier mirror;
                    # retreat it so host state is self-consistent at the
                    # READBACK emission below (the lane is released right
                    # after, but per-action audits run in between)
                    self._positions[lane] -= 1
                    continue  # discard the post-finish/post-poison token
                req = self._active[lane]
                if fin2 is not None and not bool(fin2[lane]):
                    quarantined.append(req)
                    continue
                req.out.append(int(arr2[lane]))
                req.position += 1
                self._tokens[lane] = arr2[lane]
                if req.position >= eng.max_seq_len - 1:
                    req.done = True
                if self._finish_due(req):
                    finishing.append(req)
            self._emit_action(
                ActionType.READBACK, lanes=list(lanes2),
                lag=self._last_readback_lag, lame_duck=True,
            )
        for req in finishing:
            self._maybe_finish(req)
        for req in quarantined:
            self._quarantine(req, "decode")

    def _drain_pending(self) -> None:
        """Retire the in-flight lookahead step (if any) before the
        scheduler mutates lane state. After this, readback lag is zero and
        full-lane resident syncs are legal again."""
        if self._pending is None:
            return
        pending, self._pending = self._pending, None
        self._read_and_apply(pending)

    def _lookahead_blocker(self) -> Optional[str]:
        """The scheduler event that makes this step a drained one, or None
        when its admission and prefill phases could do nothing and no
        finish is due that the host can count. A queue with no free lane
        is no event: it is the test :meth:`_admit` opens with. The names
        are the ``ServingMetrics.lookahead_declined_*`` suffixes."""
        if self._queue and self._free_lanes:
            return "admit"
        if any(r.prefilling for r in self._active.values()):
            return "prefill"
        if self._pending is not None:
            # the token in flight is some lane's last by count: drain, so
            # the lane is released and the queue's head admitted in the
            # step the synchronous sequence would do it. Only EOS is learnt
            # a step late (the lame-duck step of _read_and_apply).
            for lane in self._pending[1]:
                req = self._active.get(lane)
                if req is not None and (
                    len(req.out) + 1 >= self.gen.max_new_tokens
                    or req.position + 1 >= self.engine.max_seq_len - 1
                ):
                    return "finish"
        return None

    def _async_eligible(self) -> bool:
        """Nothing for the scheduler to do this step except advance decode
        lanes: the look-ahead dispatch may run."""
        return bool(self._active) and self._lookahead_blocker() is None

    def _note_declined(self) -> None:
        """Book a decode step that was not dispatched ahead to the rule
        that drained it (none when a policy chose the drained sequence on
        an eligible step)."""
        if self._declined is not None:
            self.metrics.note_lookahead_declined(self._declined)

    def _step_async(self) -> bool:
        """One lookahead decode step: dispatch step N+1 entirely from
        device-resident state (zero host→device uploads), then read back
        step N's tokens — which the device finished computing while the
        host was scheduling — for EOS/max-len detection one step late."""
        self._flush_state()
        decode_lanes = [
            l for l, r in self._active.items() if not r.prefilling
        ]
        self._chaos_device("decode", decode_lanes)
        eng = self.engine
        kv_need = int(max(self._positions[l] for l in decode_lanes)) + 1
        kv_limit = self._kv_bucket(kv_need)
        fn = self._decode_program(self._decode_cfg(), kv_limit)
        self.metrics.state_kernel_steps += self._state_kernel
        self.metrics.note_decode_dispatch(
            kv_limit, kv_need,
            *(self._flops_by_key.get(fn.key) or (0.0, 0.0)),
        )
        if self._fused:
            # the ENTIRE argument list is device-resident: sampled traffic
            # dispatches with the same zero uploads greedy traffic does
            args = (
                eng.params, self.cache, self._d_tokens, self._d_positions,
                self._d_tables, self._d_temps, self._d_topks,
                self._d_topps, self._d_rng,
            )
        else:
            self._key, k = jax.random.split(self._key)
            args = (
                eng.params, self.cache, self._d_tokens, self._d_positions,
                self._d_tables, k,
            )
        smode = self._note_sampling_dispatch()
        tr = self.tracer
        t_d = tr.now() if tr.enabled else 0.0
        finite = None
        if self._check_logits:
            toks, finite, self._d_positions, self.cache = fn(
                *args, self._nan_mask(decode_lanes, "decode"),
            )
        else:
            toks, self._d_positions, self.cache = fn(*args)
        if tr.enabled:
            tr.complete(
                "dispatch", t_d, program=program_label(fn), mode="async",
                sampling=smode, lanes=len(decode_lanes), kv_bucket=kv_limit,
                kv_pad=kv_limit - kv_need,
                **self._decode_rows(decode_lanes),
            )
        self._d_tokens = toks
        self._dispatch_count += 1
        self._emit_action(
            ActionType.DECODE_DISPATCH, mode="async",
            lanes=list(decode_lanes), kv=kv_limit,
        )
        prev, self._pending = self._pending, (
            toks, decode_lanes, self._dispatch_count, finite,
        )
        for lane in decode_lanes:
            self._positions[lane] += 1  # mirror the on-device advance
        self.metrics.decode_steps += 1
        self.metrics.decode_steps_async += 1
        if prev is not None:
            self._read_and_apply(prev)
        return bool(self._active or self._queue)

    def _dispatch_sync_decode(self) -> bool:
        """The decode tail of a synchronous step (shared with the
        speculative step's plain-decode fallback): back the write rows,
        flush lane state, dispatch one T=1 step and read it back."""
        if not any(not r.prefilling for r in self._active.values()):
            return bool(self._active or self._queue)
        self._ensure_decode_blocks()
        decode_lanes = [
            l for l, r in self._active.items() if not r.prefilling
        ]
        if not decode_lanes:
            return bool(self._active or self._queue)  # re-admit next step
        self._chaos_device("decode", decode_lanes)
        self._flush_state()
        eng = self.engine
        kv_need = int(max(self._positions[l] for l in decode_lanes)) + 1
        kv_limit = self._kv_bucket(kv_need)
        fn = self._decode_program(self._decode_cfg(), kv_limit)
        self.metrics.state_kernel_steps += self._state_kernel
        self.metrics.note_decode_dispatch(
            kv_limit, kv_need,
            *(self._flops_by_key.get(fn.key) or (0.0, 0.0)),
        )
        if self._fused:
            # the ENTIRE argument list is device-resident: sampled traffic
            # dispatches with the same zero uploads greedy traffic does
            args = (
                eng.params, self.cache, self._d_tokens, self._d_positions,
                self._d_tables, self._d_temps, self._d_topks,
                self._d_topps, self._d_rng,
            )
        else:
            self._key, k = jax.random.split(self._key)
            args = (
                eng.params, self.cache, self._d_tokens, self._d_positions,
                self._d_tables, k,
            )
        smode = self._note_sampling_dispatch()
        tr = self.tracer
        t_d = tr.now() if tr.enabled else 0.0
        finite = None
        if self._check_logits:
            toks, finite, self._d_positions, self.cache = fn(
                *args, self._nan_mask(decode_lanes, "decode"),
            )
        else:
            toks, self._d_positions, self.cache = fn(*args)
        if tr.enabled:
            tr.complete(
                "dispatch", t_d, program=program_label(fn), mode="sync",
                sampling=smode, lanes=len(decode_lanes), kv_bucket=kv_limit,
                kv_pad=kv_limit - kv_need,
                **self._decode_rows(decode_lanes),
            )
        self._d_tokens = toks
        self._dispatch_count += 1
        self._emit_action(
            ActionType.DECODE_DISPATCH, mode="sync",
            lanes=list(decode_lanes), kv=kv_limit,
        )
        for lane in decode_lanes:
            self._positions[lane] += 1
        self.metrics.decode_steps += 1
        self._note_declined()
        self._read_and_apply((toks, decode_lanes, self._dispatch_count, finite))
        return bool(self._active or self._queue)

    # -- speculative decoding ----------------------------------------------

    def _collect_drafts(self) -> Dict[int, List[int]]:
        """Ask the drafter for up to ``spec_draft_tokens`` proposals per
        decode-ready lane. A lane abstains when the drafter finds nothing,
        when it is spec-disabled (low accept rate past probation), or when
        fewer than two tokens remain (a plain step finishes it anyway).
        Draft counts are clamped so acceptance can never overshoot
        ``max_new_tokens`` — with the submit() capacity invariant that also
        keeps every committed row below ``max_seq_len``."""
        k = self._spec_k
        out: Dict[int, List[int]] = {}
        for lane, req in self._active.items():
            if req.prefilling or req.spec_disabled:
                continue
            remaining = self.gen.max_new_tokens - len(req.out)
            limit = min(k, remaining - 1)
            if limit < 1:
                continue
            try:
                if self.injector is not None:
                    self.injector.drafter_fault()
                drafts = self.drafter.propose(req.prompt + req.out, limit)
            except Exception as exc:
                # drafting is advisory: a drafter bug (or injected fault)
                # costs this lane its speculation for one step, never the
                # request — the lane degrades to a plain decode step
                self.metrics.drafter_faults += 1
                self._note_event()
                logger.warning(
                    "drafter failed for request %d: %s", req.rid, exc
                )
                continue
            if drafts:
                out[lane] = list(drafts[:limit])
        return out

    def _collect_tree_drafts(self) -> Dict[int, tuple]:
        """Tree-speculation sibling of :meth:`_collect_drafts`: ask the
        drafter for a packed candidate tree per decode-ready lane —
        ``lane -> (tokens, parents)`` with token ``i`` = packed node
        ``i + 1`` and ``parents[i]`` its parent's packed index (0 = the
        resident root). Drafters without ``propose_tree`` degrade to a
        single chain from ``propose`` (token-identical to linear
        speculation); abstention, the node budget (``min(spec_draft_tokens,
        remaining - 1)`` — tree depth <= node count, so acceptance can
        never overshoot ``max_new_tokens``) and the advisory failure
        contract are exactly the linear collector's."""
        k = self._spec_k
        branches = self.paged.spec_tree_branches
        propose_tree = getattr(self.drafter, "propose_tree", None)
        out: Dict[int, tuple] = {}
        for lane, req in self._active.items():
            if req.prefilling or req.spec_disabled:
                continue
            remaining = self.gen.max_new_tokens - len(req.out)
            limit = min(k, remaining - 1)
            if limit < 1:
                continue
            try:
                if self.injector is not None:
                    self.injector.drafter_fault()
                history = req.prompt + req.out
                if propose_tree is not None:
                    tokens, parents = propose_tree(history, limit, branches)
                else:
                    tokens = list(self.drafter.propose(history, limit))
                    parents = list(range(len(tokens)))
            except Exception as exc:
                self.metrics.drafter_faults += 1
                self._note_event()
                logger.warning(
                    "drafter failed for request %d: %s", req.rid, exc
                )
                continue
            if tokens:
                # a trailing trim is always topology-safe: packed order
                # puts every parent before its children
                out[lane] = (list(tokens[:limit]), list(parents[:limit]))
        return out

    def _prepare_spec_blocks(self, proposals: Dict[int, List[int]]) -> None:
        """Back each drafting lane's verify-write rows (``position ..
        position + draft_len``) with real blocks WITHOUT preempting:
        evicting cached LRU blocks is fine, but when the pool runs dry the
        lane's draft is trimmed to the rows already backed (down to a plain
        decode) — speculation is a throughput bet, never worth bumping an
        active request. Rows past ``draft_len`` stay null-backed: their
        garbage writes land in the null block and ``accept <= draft_len``
        keeps every accepted query inside the backed frontier."""
        bs = self.paged.block_size
        for lane in sorted(proposals):
            req = self._active[lane]
            need = (int(self._positions[lane]) + len(proposals[lane])) // bs + 1
            while len(req.table) < need:
                nb = self.allocator.alloc()
                if nb is None:
                    break
                self._append_block(lane, req, nb)
            backed = len(req.table) * bs - 1 - int(self._positions[lane])
            if backed < len(proposals[lane]):
                if backed < 1:
                    del proposals[lane]
                else:
                    proposals[lane] = proposals[lane][:backed]

    def _verify_phase(self) -> bool:
        """The VERIFY action body: one multi-token verify dispatch
        (``LlamaDecode.verify_step``) for every decode lane — drafting
        lanes advance by their on-device accept length + 1, lanes whose
        drafter abstained carry ``draft_len 0`` and take what is exactly a
        plain greedy decode step. Verify needs same-step readback (the
        accept length decides how far each lane's host state advances), so
        the legality automaton requires the lookahead drained before this
        action. Returns ``drafted``: False means nothing was dispatched
        (the drafter abstained everywhere or backing preempted every
        drafting lane) and the policy is expected to schedule a plain
        decode instead.

        Under ``spec_tree`` the draft is a packed candidate tree per lane
        (:meth:`_collect_tree_drafts`) dispatched through the ``ptree``
        program — the whole tree (tokens + topology + live count) rides
        one packed upload, and accept lengths are root-path depths."""
        tree = self._spec_tree
        tree_parents: Dict[int, List[int]] = {}
        if tree:
            collected = self._collect_tree_drafts()
            proposals = {l: tp[0] for l, tp in collected.items()}
            tree_parents = {l: tp[1] for l, tp in collected.items()}
        else:
            proposals = self._collect_drafts()
        if proposals:
            self._prepare_spec_blocks(proposals)
        if proposals:
            self._ensure_decode_blocks()
            # base-row backing may have preempted drafting lanes (youngest
            # first); their proposals die with them
            proposals = {
                l: d for l, d in proposals.items()
                if self._active.get(l) is not None
                and not self._active[l].prefilling
            }
        if not proposals:
            return False
        decode_lanes = [
            l for l, r in self._active.items() if not r.prefilling
        ]
        self._chaos_device("verify", decode_lanes)
        self._flush_state()
        eng = self.engine
        k = self._spec_k
        draft_len = np.zeros((eng.max_batch,), np.int32)
        if tree:
            # one packed (B, 2k+1) payload: [drafts | parents | live nodes]
            payload = np.zeros((eng.max_batch, 2 * k + 1), np.int32)
            for lane, d in proposals.items():
                pars = tree_parents[lane][: len(d)]
                payload[lane, : len(d)] = d
                payload[lane, k : k + len(pars)] = pars
                payload[lane, 2 * k] = len(d)
                draft_len[lane] = len(d)
        else:
            drafts = np.zeros((eng.max_batch, k), np.int32)
            for lane, d in proposals.items():
                drafts[lane, : len(d)] = d
                draft_len[lane] = len(d)
        kv_need = int(max(self._positions[l] for l in decode_lanes)) + k + 1
        kv_limit = self._kv_bucket(kv_need)
        fn = (
            self._tree_program(kv_limit, k)
            if tree else self._verify_program(kv_limit, k)
        )
        self.metrics.note_decode_dispatch(
            kv_limit, kv_need,
            *(self._flops_by_key.get(fn.key) or (0.0, 0.0)),
        )
        smode = self._note_sampling_dispatch()
        tr = self.tracer
        t_d = tr.now() if tr.enabled else 0.0
        args = (
            eng.params, self.cache,
            self._d_tokens, self._d_positions, self._d_tables,
        ) + (
            (self._upload(payload),)
            if tree
            else (self._upload(drafts), self._upload(draft_len))
        )
        if self._fused:
            # sampled verify: accept targets become position-keyed draws
            # from the same residents plain decode samples with
            args += (
                self._d_temps, self._d_topks, self._d_topps, self._d_rng,
            )
        if self._check_logits:
            (
                emitted_d, accept_d, new_tokens, self._d_positions,
                finite_d, self.cache,
            ) = fn(*args, self._nan_mask(decode_lanes, "verify"))
        else:
            finite_d = None
            emitted_d, accept_d, new_tokens, self._d_positions, self.cache = (
                fn(*args)
            )
        if tr.enabled:
            tr.complete(
                "dispatch", t_d, program=program_label(fn), mode="verify",
                sampling=smode, lanes=len(decode_lanes),
                drafts=int(draft_len.sum()), tree=tree,
                kv_bucket=kv_limit, kv_pad=kv_limit - kv_need,
            )
        self._d_tokens = new_tokens
        self._dispatch_count += 1
        if tree:
            self._emit_action(
                ActionType.VERIFY, lanes=list(decode_lanes), k=k,
                drafts=int(draft_len.sum()), kv=kv_limit,
                tree=True, nodes=int(draft_len.sum()),
            )
        else:
            self._emit_action(
                ActionType.VERIFY, lanes=list(decode_lanes), k=k,
                drafts=int(draft_len.sum()), kv=kv_limit,
            )
        self.metrics.decode_steps += 1
        self.metrics.verify_steps += 1
        self.metrics.draft_tokens += int(draft_len.sum())
        if tree:
            self.metrics.tree_verify_steps += 1
            self.metrics.tree_draft_tokens += int(draft_len.sum())
        emitted = self._read_tokens(emitted_d)      # (B, k+1)
        accept = self._read_tokens(accept_d)        # (B,)
        fin = None if finite_d is None else self._read_tokens(finite_d)
        self._last_readback_lag = 0
        cfg = self.paged
        finishing: List[_PagedRequest] = []
        quarantined: List[_PagedRequest] = []
        for lane in decode_lanes:
            req = self._active[lane]
            if fin is not None and not bool(fin[lane]):
                # poisoned verify: every emitted token and the accept
                # length are garbage — commit nothing on this lane
                quarantined.append(req)
                continue
            a = int(accept[lane])
            self.metrics.accepted_tokens += a
            if draft_len[lane]:
                self.metrics.hist_accept_len.observe(a)
                if tree:
                    self.metrics.note_tree_accept(f"t{k + 1}", a)
            req.spec_drafted += int(draft_len[lane])
            req.spec_accepted += a
            self._positions[lane] += a + 1  # mirror the on-device advance
            for j in range(a + 1):
                req.out.append(int(emitted[lane, j]))
                req.position += 1
                self._tokens[lane] = emitted[lane, j]
                if req.position >= eng.max_seq_len - 1:
                    req.done = True
                if self._finish_due(req):
                    # EOS (or a cap) inside the accepted run: the committed
                    # device rows past it are moot — the finish path resets
                    # the lane and reconciles host/device state
                    break
            if self._finish_due(req):
                finishing.append(req)
            elif (
                not req.spec_disabled
                and req.spec_drafted >= cfg.spec_probation_tokens
                and req.spec_accepted < cfg.spec_min_accept_rate * req.spec_drafted
            ):
                req.spec_disabled = True
                self.metrics.spec_disabled_lanes += 1
        for req in finishing:
            self._maybe_finish(req)
        for req in quarantined:
            self._quarantine(req, "verify")
        return True

    def _mixed_phase(self) -> bool:
        """The MIXED_DISPATCH action body (``PagedConfig.fused_step``): ONE
        ``pmixed`` dispatch advances every lane role this step. Prefilling
        lanes consume their next chunk suffix as *forced* rows — non-final
        chunks discard their sampled row exactly like psfx chunks did, the
        final chunk's last-row draw (keyed ``start + length``, the psfx
        key) is the request's next token and the program itself installs
        the lane's resident (token, position), no lane_set needed. Decode
        lanes ride as a verify block over the same grid (draft_len 0 is a
        plain decode row), so a step with prefills in flight costs one
        program dispatch instead of one psfx per prefilling lane plus a
        decode/verify. Same-step readback like verify: accept lengths and
        final-chunk tokens decide how far each lane's host state advances.
        Returns ``dispatched``: False means no lane is mid-prefill (or
        backing preempted them all) and the policy is expected to
        schedule the plain verify/decode tail instead."""
        if not self._fused_step:
            return False
        if not any(r.prefilling for r in self._active.values()):
            return False
        t = self._mixed_t
        tree = self._spec_tree
        proposals: Dict[int, List[int]] = {}
        tree_parents: Dict[int, List[int]] = {}
        if self._spec_k:
            # mixed rows cap drafts at t - 1 (row 0 is the resident token)
            if tree:
                collected = self._collect_tree_drafts()
                proposals = {
                    l: tp[0][: t - 1] for l, tp in collected.items()
                }
                tree_parents = {l: tp[1] for l, tp in collected.items()}
            else:
                proposals = {
                    l: d[: t - 1] for l, d in self._collect_drafts().items()
                }
            if proposals:
                self._prepare_spec_blocks(proposals)
        self._ensure_decode_blocks()
        # backing may have preempted lanes (youngest first): re-derive
        # every role set from the surviving active map
        proposals = {
            l: d for l, d in proposals.items()
            if self._active.get(l) is not None
            and not self._active[l].prefilling
        }
        forced_lanes = sorted(
            l for l, r in self._active.items() if r.prefilling
        )
        decode_lanes = [
            l for l, r in self._active.items() if not r.prefilling
        ]
        if not forced_lanes:
            return False  # every prefilling lane was preempted/failed away
        self._chaos_device("mixed", forced_lanes + decode_lanes)
        self._flush_state()
        eng = self.engine
        rows = np.zeros((eng.max_batch, t), np.int32)
        row_start = np.zeros((eng.max_batch,), np.int32)
        row_len = np.zeros((eng.max_batch,), np.int32)
        forced = np.zeros((eng.max_batch,), np.int32)
        # lane -> (req, chunk start, chunk piece, is-final-chunk)
        pieces: Dict[int, tuple] = {}
        for lane in forced_lanes:
            req = self._active[lane]
            seq = req.prompt + req.out
            start = req.prefill_pos
            piece = seq[start: start + t]
            pieces[lane] = (
                req, start, piece, start + len(piece) >= req.prefill_target,
            )
            rows[lane, : len(piece)] = piece
            row_start[lane] = start
            row_len[lane] = len(piece)
            forced[lane] = 1
        for lane, d in proposals.items():
            rows[lane, : len(d)] = d
            row_len[lane] = len(d)
        if tree:
            # per-lane packed topology: node j = rows[j-1], parent indices
            # in node space (0 = the resident root). Forced lanes don't
            # read theirs — mixed_step steers them onto the chain.
            parents_arr = np.zeros((eng.max_batch, t), np.int32)
            for lane, d in proposals.items():
                pars = tree_parents[lane][: len(d)]
                parents_arr[lane, 1 : 1 + len(pars)] = pars
        kv_need = max(
            max(start for _, start, _, _ in pieces.values()),
            max(
                (int(self._positions[l]) for l in decode_lanes), default=0
            ),
        ) + t
        kv_limit = self._kv_bucket(kv_need)
        fn = self._mixed_program(t, kv_limit)
        self.metrics.note_decode_dispatch(
            kv_limit, kv_need,
            *(self._flops_by_key.get(fn.key) or (0.0, 0.0)),
        )
        smode = self._note_sampling_dispatch()
        tr = self.tracer
        t_d = time.perf_counter()
        args = (
            eng.params, self.cache,
            self._d_tokens, self._d_positions, self._d_tables,
            self._upload(rows), self._upload(row_start),
            self._upload(row_len), self._upload(forced),
        )
        if tree:
            args += (self._upload(parents_arr),)
        if self._fused:
            args += (
                self._d_temps, self._d_topks, self._d_topps, self._d_rng,
            )
        if self._check_logits:
            (
                emitted_d, accept_d, new_tokens, self._d_positions,
                finite_d, self.cache,
            ) = fn(
                *args,
                self._nan_mask(forced_lanes + decode_lanes, "mixed"),
            )
        else:
            finite_d = None
            emitted_d, accept_d, new_tokens, self._d_positions, self.cache = (
                fn(*args)
            )
        t_d1 = time.perf_counter()
        if tr.enabled:
            # the row-role breakdown IS the trace payload: how many packed
            # rows each role contributed to this one dispatch
            tr.complete(
                "dispatch", t_d, t_d1, program=program_label(fn),
                mode="mixed", sampling=smode,
                lanes=len(forced_lanes) + len(decode_lanes),
                decode_rows=len(decode_lanes) - len(proposals),
                verify_rows=len(proposals),
                prefill_rows=len(forced_lanes),
                prefill_tokens=sum(len(p) for _, _, p, _ in pieces.values()),
                drafts=sum(len(d) for d in proposals.values()),
                kv_bucket=kv_limit, kv_pad=kv_limit - kv_need,
            )
        self._d_tokens = new_tokens
        self._dispatch_count += 1
        self.metrics.mixed_dispatches += 1
        self._emit_action(
            ActionType.MIXED_DISPATCH,
            lanes=list(decode_lanes), prefill_lanes=list(forced_lanes),
            drafts=sum(len(d) for d in proposals.values()), kv=kv_limit,
        )
        if decode_lanes:
            self.metrics.decode_steps += 1
        if proposals:
            self.metrics.verify_steps += 1
            self.metrics.draft_tokens += sum(
                len(d) for d in proposals.values()
            )
            if tree:
                self.metrics.tree_verify_steps += 1
                self.metrics.tree_draft_tokens += sum(
                    len(d) for d in proposals.values()
                )
        emitted = self._read_tokens(emitted_d)      # (B, t)
        accept = self._read_tokens(accept_d)        # (B,)
        fin = None if finite_d is None else self._read_tokens(finite_d)
        self._last_readback_lag = 0
        cfg = self.paged
        bs = cfg.block_size
        wall_ms = (t_d1 - t_d) * 1e3
        finishing: List[_PagedRequest] = []
        quarantined: List[_PagedRequest] = []
        for lane, (req, start, piece, final) in pieces.items():
            if fin is not None and not bool(fin[lane]):
                quarantined.append(req)
                continue
            req.prefill_pos = start + len(piece)
            req.prefill_ms += wall_ms
            self.metrics.prefill_tokens += len(piece)
            self.metrics.prefill_chunks += 1
            if not final:
                # the device resident advanced to (garbage draw, next
                # chunk start); the next forced dispatch re-keys off the
                # uploaded row_start, so the host position mirror stays
                # parked at the post-prompt row admission installed
                continue
            # final chunk: the program already wrote the lane's resident
            # (sampled token, position) — mirror them host-side, commit
            # the first token, register the prompt for prefix sharing
            tok = int(emitted[lane, len(piece) - 1])
            req.prefilling = False
            req.table_dev = None
            req.out.append(tok)
            req.position = req.prefill_target
            self._note_first_token(req)
            self.tracer.request_state(req.rid, "active")
            self._tokens[lane] = tok
            self._positions[lane] = req.position
            if self._share_prefixes:
                seq = req.prompt + req.out[:-1]
                n_full = len(seq) // bs
                if n_full:
                    self.index.insert(seq[: n_full * bs], req.table[:n_full])
            if self._finish_due(req):
                finishing.append(req)
        for lane in decode_lanes:
            req = self._active[lane]
            if fin is not None and not bool(fin[lane]):
                quarantined.append(req)
                continue
            a = int(accept[lane])
            dl = int(row_len[lane])
            self.metrics.accepted_tokens += a
            if dl:
                self.metrics.hist_accept_len.observe(a)
                if tree:
                    self.metrics.note_tree_accept(f"t{t}", a)
            req.spec_drafted += dl
            req.spec_accepted += a
            self._positions[lane] += a + 1  # mirror the on-device advance
            for j in range(a + 1):
                req.out.append(int(emitted[lane, j]))
                req.position += 1
                self._tokens[lane] = emitted[lane, j]
                if req.position >= eng.max_seq_len - 1:
                    req.done = True
                if self._finish_due(req):
                    break
            if self._finish_due(req):
                finishing.append(req)
            elif (
                not req.spec_disabled
                and req.spec_drafted >= cfg.spec_probation_tokens
                and req.spec_accepted < cfg.spec_min_accept_rate * req.spec_drafted
            ):
                req.spec_disabled = True
                self.metrics.spec_disabled_lanes += 1
        for req in finishing:
            self._maybe_finish(req)
        for req in quarantined:
            self._quarantine(req, "mixed")
        return True

    # backstop against a runaway policy generator (the explorer drives
    # arbitrary third-party schedules through this loop)
    _MAX_ACTIONS_PER_STEP = 64

    def _execute_action(self, act: StepAction) -> None:
        """Run one policy-scheduled action. Engine-internal transitions
        (PREEMPT/FINISH/flushes) are consequences of these, never directly
        schedulable — a policy yielding one is a programming error."""
        t = act.type
        if t is ActionType.READBACK:
            self._drain_pending()
        elif t is ActionType.ADMIT:
            # graftserve: a policy may rank the waiting queue before the
            # wave runs (meta["admit_order"] = rids, from view.queued()).
            # The wave itself is unchanged — still strict head-of-line
            # over the (re)ordered queue, so block accounting and the
            # admit_blocked semantics are identical.
            order = act.meta.get("admit_order") if act.meta else None
            if order is not None:
                self._reorder_queue(order)
            self._admit()
        elif t is ActionType.PREFILL_CHUNK:
            if self._fused_step:
                # fused mode never dispatches psfx (the keys are not even
                # in the catalog): a fused-unaware policy's PREFILL_CHUNK
                # routes to the mixed program instead
                self._last_mixed_dispatched = self._mixed_phase()
            else:
                budget = act.meta.get("budget_tokens") if act.meta else None
                self._advance_prefills(budget_tokens=budget)
        elif t is ActionType.VERIFY:
            # drafting needs same-step readback: whatever decodes in this
            # step, the verify program or the plain tail, was held by it
            self._declined = "spec"
            self._last_verify_drafted = self._verify_phase()
            if self._last_verify_drafted:
                self._note_declined()
        elif t is ActionType.MIXED_DISPATCH:
            self._last_mixed_dispatched = self._mixed_phase()
            if self._last_mixed_dispatched:
                self._note_declined()
        elif t is ActionType.DECODE_DISPATCH:
            if act.mode == "async":
                if self._ensure_decode_blocks_async():
                    self._last_async_fell_back = False
                    self._step_async()
                else:
                    # Pool dry: the scheduler must preempt, which mutates
                    # lane state — the policy reads this outcome and drops
                    # to the synchronous sequence for this step.
                    self._last_async_fell_back = True
                    self._declined = "pool"
            else:
                self._dispatch_sync_decode()
        elif t is ActionType.AUDIT:
            self._audit(strict=False)
        else:
            raise ValueError(
                f"policy scheduled engine-internal action {t.value}; "
                f"schedulable actions: "
                f"{sorted(a.value for a in POLICY_ACTIONS)}"
            )

    def _step_inner(self) -> bool:
        # the step schedule comes from the policy (serving/policy.py):
        # each yielded action executes before the generator resumes, so
        # the policy reads post-action outcomes (view.last_*) to decide
        # data-dependent fallbacks. The degradation ladder's rung 1/2
        # shedding is a policy decision too (FifoPolicy reads
        # view.degrade_level); rung 3 — the paged kernel — is applied at
        # program selection, rung 4 at _update_ladder.
        self._declined = (
            "ladder" if self._degrade_level >= 2 else self._lookahead_blocker()
        )
        n = 0
        for act in self.policy.actions(self._view):
            n += 1
            if n > self._MAX_ACTIONS_PER_STEP:
                raise RuntimeError(
                    f"step policy {self.policy.name!r} exceeded "
                    f"{self._MAX_ACTIONS_PER_STEP} actions in one step"
                )
            self._execute_action(act)
        return bool(self._active or self._queue)

    def step(self) -> bool:
        """Execute one step *schedule*: the configured :class:`StepPolicy`
        (serving/policy.py) yields a sequence of typed actions over the
        alphabet {ADMIT, PREFILL_CHUNK, DECODE_DISPATCH, READBACK, VERIFY,
        AUDIT} and the engine runs them in order, recording every executed
        action — plus the engine-internal PREEMPT / FINISH /
        LANE_SET_FLUSH / TABLE_DELTA_FLUSH transitions — into the bounded
        ``action_trace`` that analysis/graftsched.py replays against the
        schedule legality automaton (GC010). The default FifoPolicy order:
        admit waiting requests, push one prefill chunk per prefilling
        lane, then advance every decode-ready lane one token — so a long
        prompt's chunks interleave with the existing streams' decode
        steps. Pool exhaustion preempts-and-requeues instead of raising.
        Wherever the scheduler has nothing to do the decode path runs one
        program ahead of the device (docs/serving.md "How the engine
        steps"); per-request state then trails the device by one step
        until a scheduler event drains the pipeline. Returns False when
        nothing is left to do.

        Failure domains: an injected device fault aborts only its victim
        lanes (terminal ``failed`` status, blocks released, survivors
        redispatch from untouched resident state); repeated faults or
        sustained pool pressure climb the degradation ladder; a configured
        ``stall_step_limit`` raises :class:`EngineStalledError` instead of
        letting :meth:`run_to_completion` spin on a wedged lane."""
        t0 = time.perf_counter()
        self._wait_ms = 0.0
        self._step_index += 1
        # dispatches_per_step denominator: every step() counts, so the
        # fused-vs-unfused dispatch reduction is visible per engine step
        self.metrics.engine_steps += 1
        # fresh per-step action record; everything _emit_action sees until
        # the next step() — including _update_ladder preemptions and fault
        # recovery below — lands in this step's trace entry
        self._step_actions = []
        self.action_trace.append(
            (self._step_index, self._pending is not None, self._step_actions)
        )
        self.tracer.begin_step(self._step_index)
        if self.injector is not None:
            self.injector.begin_step(self._step_index)
        try:
            alive = self._step_inner()
        except InjectedFault as fault:
            alive = self._recover_fault(fault)
        if self._spill_pending:
            # tiered KV: commit this step's spill snapshots to the host
            # tier — the step's device work is already in flight, so the
            # blocking D2H copies overlap it; nothing here dispatches or
            # uploads (GC003's zero-upload steady state holds)
            self._drain_spills()
        if self.injector is not None:
            self.metrics.faults_injected = self.injector.total_fired
        total_ms = (time.perf_counter() - t0) * 1e3
        self.metrics.device_wait_ms += self._wait_ms
        self.metrics.host_schedule_ms += max(total_ms - self._wait_ms, 0.0)
        self.metrics.hist_step_ms.observe(total_ms)
        self.metrics.hist_queue_depth.observe(len(self._queue))
        self.metrics.queued_requests = len(self._queue)
        if self._slo is not None:
            # SLO burn evaluation BEFORE the ladder update so a raised
            # alert's _note_event lands in the same step's event window
            self._slo.on_step(
                self._step_index, tracer=self.tracer,
                note_event=self._note_event,
            )
        self._update_ladder()
        if (
            self.paged.audit_interval
            and self._step_index % self.paged.audit_interval == 0
        ):
            self._audit(strict=False)
        every = self.paged.metrics_log_every
        steps = self.metrics.decode_steps
        if every and steps and steps % every == 0 and steps != self._last_log_step:
            self._last_log_step = steps
            self.metrics.log(logger, self.allocator, self.index)
        self._check_stall()
        self.tracer.end_step(
            queue=len(self._queue), active=len(self._active),
            wait_ms=round(self._wait_ms, 3),
        )
        return alive

    def export_trace(self, path: str, fmt: str = "chrome") -> str:
        """Write the graftscope flight recorder (last
        ``trace_buffer_steps`` steps + every request span) to ``path`` —
        ``fmt="chrome"`` for trace-event JSON (load in chrome://tracing or
        https://ui.perfetto.dev), ``"jsonl"`` for line-delimited events.
        Requires ``PagedConfig.trace_enabled`` (the file is valid but
        empty otherwise)."""
        return self.tracer.export(path, fmt=fmt)

    def run_to_completion(self) -> Dict[int, List[int]]:
        """Step until idle. Requests that failed terminally (chaos, NaN
        quarantine) are included with their partial output — check
        ``request_info(rid)["status"]`` to tell them apart. Bounded by the
        stall watchdog when ``PagedConfig.stall_step_limit`` is set."""
        while self.step():
            pass
        return {rid: r.out for rid, r in sorted(self._finished.items())}

    @staticmethod
    def _status(req: _PagedRequest) -> str:
        """Lifecycle status ∈ {queued, prefilling, active, preempted,
        finished, failed}."""
        if req.failed:
            return "failed"
        if req.done:
            return "finished"
        if req.lane is None:
            return "preempted" if req.preemptions else "queued"
        return "prefilling" if req.prefilling else "active"

    def request_tokens(self, rid: int) -> List[int]:
        """Copy of the tokens generated so far for ``rid``, in any
        lifecycle state — the graftserve streaming path diffs this
        between steps to emit token deltas. O(tokens); never blocks on
        the device (``out`` is host state committed by readbacks)."""
        req = self._requests.get(rid)
        if req is None:
            raise KeyError(f"unknown request id {rid}")
        return list(req.out)

    def request_info(self, rid: int) -> dict:
        """Per-request serving stats (``cached_tokens`` is the per-request
        prefix-cache report the protocol layer surfaces). O(1): every
        request lives in ``_requests`` from submit() on, whatever lifecycle
        state it is in. ``status`` is the lifecycle state; ``error`` holds
        the failure detail for ``status == "failed"`` (else None). The
        ``done``/``prefilling`` booleans predate ``status`` and are kept
        for callers that grew around them."""
        req = self._requests.get(rid)
        if req is None:
            raise KeyError(f"unknown request id {rid}")
        # timing context survives into the terminal record: finished AND
        # failed requests report ttft/queue/prefill (and tpot once >= 2
        # tokens exist); fields not reached yet are None
        ttft_ms = None
        if req.first_token_at is not None:
            ttft_ms = round((req.first_token_at - req.submitted_at) * 1e3, 3)
        tpot_ms = None
        if (
            req.finished_at is not None
            and req.first_token_at is not None
            and len(req.out) > 1
        ):
            tpot_ms = round(
                (req.finished_at - req.first_token_at) * 1e3
                / (len(req.out) - 1), 3,
            )
        queue_ms = None
        if req.admitted_at is not None:
            queue_ms = round((req.admitted_at - req.submitted_at) * 1e3, 3)
        return {
            "rid": req.rid,
            "prompt_tokens": len(req.prompt),
            "generated_tokens": len(req.out),
            "cached_tokens": req.cached_tokens,
            "preemptions": req.preemptions,
            "prefilling": req.prefilling,
            "done": req.done,
            "status": self._status(req),
            "error": req.error,
            "service_class": req.service_class,
            "tenant": req.tenant,
            "submitted_at": req.submitted_at,
            "first_token_at": req.first_token_at,
            "finished_at": req.finished_at,
            "queue_ms": queue_ms,
            "prefill_ms": round(req.prefill_ms, 3),
            "ttft_ms": ttft_ms,
            "tpot_ms": tpot_ms,
        }


def make_serving_engine(
    engine: InferenceEngine,
    gen: GenerationConfig = GenerationConfig(),
    paged: Optional[PagedConfig] = None,
    drafter: Optional[Any] = None,
    injector: Optional[FaultInjector] = None,
):
    """The serving-path config flag: ``paged=None`` keeps the dense
    slot-scheduled engine; a :class:`PagedConfig` opts into the block pool
    + radix prefix caching (``drafter`` overrides the default n-gram
    proposer when ``spec_draft_tokens`` is set; ``injector`` hooks a chaos
    :class:`FaultInjector` into the paged engine's funnels)."""
    if paged is None:
        if injector is not None:
            raise ValueError("fault injection requires the paged engine")
        from neuronx_distributed_llama3_2_tpu.inference.engine import (
            ContinuousBatchingEngine,
        )

        return ContinuousBatchingEngine(engine, gen)
    return PagedServingEngine(
        engine, gen, paged, drafter=drafter, injector=injector,
    )
