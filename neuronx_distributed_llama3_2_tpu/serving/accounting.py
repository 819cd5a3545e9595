"""graftmeter: static device-cost accounting for the paged serving engine.

The device-side half of observability (docs/serving.md "Cost accounting
& SLOs"). graftscope (serving/tracing.py, serving/metrics.py) answers
*when* the engine did things; this module answers *what they cost*:

- a per-program :class:`CostProfile` harvested from every
  :class:`~.engine.ProgramRecord` in the registry — XLA's own
  ``cost_analysis()`` FLOP/byte figures off the re-lowered program (a
  trace-cache hit, no compile, ~ms per program) plus argument/output HBM
  sizes computed from the recorded example avals, with an analytic
  formula (the shared :mod:`~neuronx_distributed_llama3_2_tpu.flops`
  estimator) as the fallback when XLA reports nothing;
- an :class:`HBMLedger` summing the KV pool (scales included), the
  per-rank parameter shard, the resident token/position/table arrays and
  the largest program workspace into a footprint + headroom figure
  against the device's HBM budget;
- backend-independent **analytic profiles** computed from catalog keys
  alone (no dispatch, no lowering) — what the graftcheck gate's golden
  cost table (``scripts/graftcheck_costs.txt``) pins, so the table is
  byte-stable across CPU test hosts and real chips.

Everything here is static: harvest runs once at ``prewarm()`` (or on
demand via ``engine.ensure_cost_profiles()``), and the per-step cost
accounting in the engine is a dict lookup + float adds on figures
computed here. Zero per-step device work, zero uploads — the graftscope
non-interference contract extends to graftmeter.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from neuronx_distributed_llama3_2_tpu import flops as flops_mod
from neuronx_distributed_llama3_2_tpu.serving.catalog import format_key

# program kinds that run model math — these must carry nonzero FLOPs
# after harvest (the graftcheck GC009 completeness contract); the
# remaining kinds only move bytes and report their element traffic
COMPUTE_KINDS = frozenset(
    {"pctx", "psfx", "pdecode", "pverify", "ptree", "pmixed"}
)
MOVE_KINDS = frozenset(
    {"copy_block", "lane_set", "table_delta", "block_save", "block_restore"}
)

# PCIe-class host<->device link bandwidth the tiered-KV restore-vs-recompute
# crossover prices payload moves against: sustained Gen4 x16-class figure,
# not the marketing peak. The crossover compares restore bytes over this
# link against prefill FLOPs at the padded rung (engine._restore_price).
HOST_LINK_BW_BYTES_PER_S = 1.6e10


@dataclasses.dataclass(frozen=True)
class CostProfile:
    """Static cost figures for one compiled serving program.

    ``flops_source`` records provenance: ``"xla"`` (cost_analysis of the
    lowered program), ``"analytic"`` (the shared FLOP formula — compute
    kinds whose backend reported nothing), or ``"analytic-move"``
    (data-movement kinds, where "flops" counts elements moved so every
    profile is nonzero without polluting MFU — the engine only folds
    COMPUTE_KINDS figures into its dispatched-FLOP counters).
    """

    key: tuple
    kind: str
    flops: float
    bytes_accessed: float
    argument_bytes: int
    output_bytes: int
    temp_bytes: int = 0          # populated only by a deep (compiled) harvest
    flops_source: str = "analytic"

    @property
    def label(self) -> str:
        return format_key(self.key)

    def arithmetic_intensity(self) -> float:
        """FLOPs per byte accessed — the roofline x-coordinate."""
        return self.flops / max(self.bytes_accessed, 1.0)

    def roofline_mfu(
        self,
        peak_flops: Optional[float] = None,
        peak_bw: Optional[float] = None,
    ) -> float:
        """Bandwidth-roofline ceiling on achievable MFU at this program's
        arithmetic intensity: below the machine balance point the program
        is bandwidth-bound and can reach at most AI/balance of peak.
        Peaks default to this process's :func:`..flops.chip_peaks` row."""
        if peak_flops is None or peak_bw is None:
            peaks = flops_mod.chip_peaks()
            peak_flops = peak_flops or peaks.bf16_flops
            peak_bw = peak_bw or peaks.hbm_bw
        balance = peak_flops / peak_bw
        return min(1.0, self.arithmetic_intensity() / balance)

    def to_dict(self) -> dict:
        return {
            "key": self.label,
            "kind": self.kind,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "flops_source": self.flops_source,
            "arithmetic_intensity": round(self.arithmetic_intensity(), 4),
            "roofline_mfu": round(self.roofline_mfu(), 6),
        }


@dataclasses.dataclass(frozen=True)
class EngineDims:
    """The static model/pool dimensions the analytic estimators need —
    captured once per engine so profile math never touches live arrays."""

    num_params: int
    param_bytes: int             # whole (unsharded) parameter bytes
    num_layers: int
    hidden_size: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int
    max_batch: int
    table_width: int
    block_size: int
    num_blocks: int
    kv_bytes_per_elem: int
    scale_bytes: int             # per-(row, kv-head) scale bytes, 0 if bf16
    tp_size: int
    quant_mxu: bool = False      # int8 q·k dot on the MXU (config.quant_mxu)
    fused_sampling: bool = False  # per-lane sampling residents in lane_set
    # arrays a cache row is spread over: k and v, or 1 for a latent pool
    # (then num_kv_heads = 1 and head_dim = the row's width)
    kv_arrays: int = 2
    # bytes one block of the pool holds on a rank, over every layer and every
    # leaf, taken off the cache itself (``metrics.pool_bytes_per_rank`` over
    # its blocks); None on dims built by hand (a planner's, an older dump's),
    # which reckon it from the row dims above
    block_bytes: Optional[int] = None
    # False where a block is one sequence's whole state and a token leaves no
    # row (``cache_is_positional`` of the decode model): a program then moves
    # whole blocks — the sequences it touches, read and written
    cache_is_positional: bool = True
    # bytes a lane holds on a rank of the kind of cache that is laid out a
    # lane (``cache_kinds`` of the decode model): a ring, over the layers that
    # keep only their last rows, or — ``ring_is_state`` — a state, over the
    # layers that keep one; 0 where every layer keeps the whole context.
    # ``block_bytes`` and ``kv_row_bytes`` are then the other layers' alone: a
    # request's cache bytes are its context's rows at ``kv_row_bytes`` plus
    # one ring or one state
    ring_bytes: int = 0
    ring_is_state: bool = False

    @classmethod
    def from_engine(cls, engine: Any) -> "EngineDims":
        import jax
        import numpy as np

        mc = engine.model.config
        positional = bool(engine.model.cache_is_positional)
        kv_arrays, kv_heads, kv_width = (
            engine.model.cache_row_dims() if positional else (0, 0, 0)
        )
        leaves = jax.tree.leaves(engine.engine.params)
        num_params = sum(int(np.prod(l.shape)) for l in leaves)
        param_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves
        )
        from neuronx_distributed_llama3_2_tpu.quantization.kv_cache import (
            kv_scale_itemsize,
        )

        # the ring kind's pool, where the cache has one: its bytes on a rank,
        # its blocks, and a lane's share of them
        ring_kind = getattr(engine, "_lane_kind", None)
        ring_blocks = int(getattr(engine, "_lane_blocks", 0))
        ring_pool_bytes = ring_pool_blocks = 0
        if ring_kind is not None:
            ring_leaves = jax.tree.leaves(engine._kind_pool(ring_kind))
            ring_pool_bytes = sum(a.addressable_shards[0].data.nbytes for a in ring_leaves)
            ring_pool_blocks = int(ring_leaves[0].shape[1])

        return cls(
            num_params=num_params,
            param_bytes=param_bytes,
            num_layers=mc.num_layers,
            hidden_size=mc.hidden_size,
            num_kv_heads=kv_heads,
            head_dim=kv_width,
            vocab_size=mc.vocab_size,
            max_batch=engine.engine.max_batch,
            table_width=engine.table_width,
            block_size=engine.paged.block_size,
            num_blocks=engine.paged.num_blocks,
            kv_bytes_per_elem=jax.tree.leaves(engine.cache)[0].dtype.itemsize,
            scale_bytes=kv_scale_itemsize(engine.paged.kv_cache_dtype),
            tp_size=max(int(engine.metrics.tp_size), 1),
            quant_mxu=bool(getattr(engine.model.config, "quant_mxu", False)),
            fused_sampling=bool(getattr(engine, "_fused", False)),
            kv_arrays=kv_arrays,
            block_bytes=(int(engine.metrics.pool_bytes_per_rank) - ring_pool_bytes)
            // int(engine.paged.num_blocks),
            cache_is_positional=positional,
            ring_bytes=ring_pool_bytes * ring_blocks // max(ring_pool_blocks, 1),
            ring_is_state=bool(ring_kind is not None and ring_kind.state),
        )

    @property
    def kv_heads_local(self) -> int:
        """KV heads resident per rank (the tp shard when it divides)."""
        if self.num_kv_heads % self.tp_size == 0:
            return max(self.num_kv_heads // self.tp_size, 1)
        return self.num_kv_heads  # replication fallback

    @property
    def param_bytes_local(self) -> int:
        """Per-rank parameter byte estimate (uniform tp shard)."""
        return self.param_bytes // self.tp_size

    def kv_row_bytes(self) -> int:
        """HBM bytes one KV row (all layers, every array, local heads) holds,
        scale arrays included when the pool is quantized; 0 where the cache
        is a state."""
        if not self.cache_is_positional:
            return 0
        if self.block_bytes is not None:
            return self.block_bytes // self.block_size
        per_head = self.head_dim * self.kv_bytes_per_elem + self.scale_bytes
        return self.kv_arrays * self.num_layers * self.kv_heads_local * per_head

    def pool_bytes_local(self) -> int:
        """Bytes of the allocator's pool on a rank (the rings are beside it:
        ``ring_bytes`` a lane)."""
        if self.block_bytes is not None:
            return self.num_blocks * self.block_bytes
        return self.num_blocks * self.block_size * self.kv_row_bytes()

    def state_bytes(self, sequences: int) -> int:
        """Bytes a program over ``sequences`` lanes moves of what a lane holds
        whole: a state — a state pool's block, a state kind's slot — read
        once and written once; a ring read once; 0 for a pool of rows alone."""
        if self.cache_is_positional:
            return (2 if self.ring_is_state else 1) * sequences * self.ring_bytes
        return 2 * sequences * (self.block_bytes or 0)

    def request_cache_bytes(self, context: int) -> int:
        """Bytes a request of ``context`` tokens holds in the cache on a
        rank: its rows in whole blocks, plus a ring or a state where a kind
        of layer keeps one a lane; a state's one block where the whole cache
        is a state."""
        if not self.cache_is_positional:
            return self.block_bytes or 0
        blocks = -(-context // self.block_size)
        return blocks * self.block_size * self.kv_row_bytes() + self.ring_bytes


def _flops_per_token(
    dims: EngineDims, context: int, quant_mxu: bool = False
) -> float:
    f = flops_mod.decode_flops_per_token(
        dims.num_params, dims.num_layers, dims.hidden_size, max(context, 1)
    )
    if quant_mxu:
        # the q·kᵀ half of the attention term (2·L·H·K of the 4·L·H·K)
        # runs as an int8 MXU dot at twice bf16 throughput — charge it
        # at half its bf16-equivalent cost, so MFU normalization keeps
        # comparing against the bf16 peak the roofline is stated in
        f -= dims.num_layers * dims.hidden_size * max(context, 1)
    return f


def analytic_cost(key: tuple, dims: EngineDims) -> Tuple[float, float, str]:
    """(flops, bytes_accessed, flops_source) for a registry/catalog key,
    from the key tuple alone — deterministic across backends, so these
    figures are what the golden cost table stores.

    Compute kinds use the shared per-token formula at the key's attention
    extent; move kinds report elements moved as their work figure
    (flops_source ``analytic-move``) so no profile is ever zero."""
    kind = key[0]
    if kind == "pctx":
        # causal prefill of a length-b bucket: token i attends i rows,
        # so the attention term integrates to b²/2
        b = int(key[1])
        f = b * 2 * dims.num_params \
            + 2 * dims.num_layers * dims.hidden_size * b * b
        rows = b
        tokens = b
        lanes = 1
    elif kind == "psfx":
        # suffix prefill: b tokens each attending up to kv_limit rows
        b, kv = int(key[1]), int(key[2])
        f = b * _flops_per_token(dims, kv)
        rows = kv
        tokens = b
        lanes = 1
    elif kind == "pdecode":
        # the decode kernel is where quant_mxu lives: its q·k dot runs
        # at int8 throughput, so the key's flop figure drops with it
        kv = int(key[2])
        f = dims.max_batch * _flops_per_token(dims, kv, dims.quant_mxu)
        rows = dims.max_batch * kv
        tokens = dims.max_batch
        lanes = dims.max_batch
    elif kind in ("pverify", "ptree"):
        # ptree (packed-tree verify) prices identically to linear verify:
        # the forward is the same B·(k+1) query rows over kv+k attention
        # extent — the ancestor mask only changes which rows each query
        # may see, not how many it streams, and a padded shallow tree
        # wastes exactly the rung's pad rows either way
        kv, k = int(key[1]), int(key[2])
        f = dims.max_batch * (k + 1) * _flops_per_token(
            dims, kv + k, dims.quant_mxu
        )
        rows = dims.max_batch * (kv + k)
        tokens = dims.max_batch * (k + 1)
        lanes = dims.max_batch
    elif kind == "pmixed":
        # fused mixed-mode step: B lanes × t query rows over the shared
        # pool — the verify formula at draft width k = t - 1 (a prefill
        # chunk row costs the same row of attention as a verify row)
        t, kv = int(key[1]), int(key[2])
        f = dims.max_batch * t * _flops_per_token(
            dims, kv + t - 1, dims.quant_mxu
        )
        rows = dims.max_batch * (kv + t - 1)
        tokens = dims.max_batch * t
        lanes = dims.max_batch
    elif kind == "copy_block":
        elems = dims.kv_arrays * dims.num_layers * dims.block_size \
            * dims.kv_heads_local * dims.head_dim
        if not dims.cache_is_positional:     # a state is copied whole
            elems = (dims.block_bytes or 0) // dims.kv_bytes_per_elem
        return float(elems), float(2 * elems * dims.kv_bytes_per_elem), \
            "analytic-move"
    elif kind == "lane_set":
        # fused sampling adds 5 per-lane resident elements to the
        # scatter: temp + top_k + top_p + the (2,) uint32 key data
        per_lane = 2 + dims.table_width \
            + (5 if dims.fused_sampling else 0)
        elems = dims.max_batch * per_lane
        return float(elems), float(2 * elems * 4), "analytic-move"
    elif kind == "table_delta":
        elems = dims.max_batch * dims.table_width
        return 1.0, float(2 * elems * 4), "analytic-move"
    elif kind in ("block_save", "block_restore"):
        # tiered KV: one block's payload crossing the pool boundary (spill
        # snapshot out / restore scatter in). Scale tiles ride with the
        # payload under quantized storage, so rows are priced at
        # kv_row_bytes — these are the figures the restore-vs-recompute
        # crossover divides by HOST_LINK_BW_BYTES_PER_S.
        elems = dims.kv_arrays * dims.num_layers * dims.block_size \
            * dims.kv_heads_local * dims.head_dim
        byts = 2 * dims.block_size * dims.kv_row_bytes()
        return float(elems), float(byts), "analytic-move"
    else:
        return 1.0, 1.0, "analytic-move"
    # compute-kind bytes: the parameter shard streams once, the touched
    # KV rows stream once (or the touched lanes' states, there and back),
    # and the logits materialize in fp32
    byts = dims.param_bytes_local + rows * dims.kv_row_bytes() \
        + dims.state_bytes(lanes) + tokens * dims.vocab_size * 4
    return float(f), float(byts), "analytic"


def analytic_profile(key: tuple, dims: EngineDims) -> CostProfile:
    """Backend-independent CostProfile from a key alone (no example avals
    needed) — the golden cost table entries and the pre-dispatch seed the
    engine registers programs with."""
    f, b, src = analytic_cost(key, dims)
    kind = str(key[0])
    if kind in COMPUTE_KINDS:
        # arguments ≈ params shard + the whole pool (every compute
        # program takes the full donated cache); outputs are the sampled
        # tokens (the cache comes back through the donation alias)
        arg = dims.param_bytes_local + dims.pool_bytes_local() \
            + dims.max_batch * dims.ring_bytes
        out = dims.max_batch * 4
    else:
        arg = dims.pool_bytes_local() // dims.num_blocks
        out = arg
    return CostProfile(
        key=key, kind=kind, flops=f, bytes_accessed=b,
        argument_bytes=int(arg), output_bytes=int(out), flops_source=src,
    )


def _leaf_bytes(tree: Any) -> int:
    """Total bytes across the aval/array leaves of a pytree (avals carry
    shape/dtype; live arrays work the same way)."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            itemsize = np.dtype(dtype).itemsize
        except TypeError:
            # extended dtypes (prng key<fry> avals): itemsize when the
            # dtype exposes one, else the threefry key payload (2×uint32)
            itemsize = int(getattr(dtype, "itemsize", 0) or 8)
        total += int(np.prod(shape, dtype=np.int64)) * itemsize
    return total


def profile_record(
    rec: Any, dims: EngineDims, deep: bool = False
) -> CostProfile:
    """CostProfile for one dispatched :class:`~.engine.ProgramRecord`.

    Default harvest re-lowers at the recorded example avals (a jit
    trace-cache hit — no compile) and reads ``Lowered.cost_analysis()``;
    argument/output HBM comes from the aval shapes. ``deep=True``
    additionally compiles the lowered program for
    ``memory_analysis().temp_size_in_bytes`` — expensive (a real XLA
    compile per program), so it is opt-in tooling, never the engine
    default."""
    a_flops, a_bytes, a_src = analytic_cost(rec.key, dims)
    arg_bytes = _leaf_bytes(rec.example_args)
    out_bytes = 0
    temp_bytes = 0
    flops, byts, src = a_flops, a_bytes, a_src
    try:
        lowered = rec.lower()
    except Exception:
        lowered = None
    if lowered is not None:
        try:
            out_bytes = _leaf_bytes(lowered.out_info)
        except Exception:
            out_bytes = 0
        ca: Any = None
        try:
            ca = lowered.cost_analysis()
        except Exception:
            ca = None
        if isinstance(ca, (list, tuple)) and ca:
            ca = ca[0]
        if isinstance(ca, dict):
            xf = float(ca.get("flops", 0.0) or 0.0)
            xb = float(ca.get("bytes accessed", 0.0) or 0.0)
            if xf > 0.0:
                flops, src = xf, "xla"
            if xb > 0.0:
                byts = xb
        if deep:
            try:
                mem = lowered.compile().memory_analysis()
                temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
            except Exception:
                temp_bytes = 0
    return CostProfile(
        key=rec.key, kind=rec.kind, flops=flops, bytes_accessed=byts,
        argument_bytes=int(arg_bytes), output_bytes=int(out_bytes),
        temp_bytes=temp_bytes, flops_source=src,
    )


def harvest_cost_profiles(
    engine: Any, deep: bool = False
) -> Dict[tuple, CostProfile]:
    """CostProfile per dispatched program in the engine's registry.
    Registered-but-never-dispatched records (no example avals) fall back
    to their analytic profile, so a prewarmed engine — where every
    catalog key HAS dispatched — always yields a complete table."""
    dims = EngineDims.from_engine(engine)
    profiles: Dict[tuple, CostProfile] = {}
    for key, rec in engine.program_registry().items():
        if rec.example_args is None:
            profiles[key] = analytic_profile(key, dims)
        else:
            profiles[key] = profile_record(rec, dims, deep=deep)
    return profiles


def analytic_profiles(engine: Any) -> Dict[tuple, CostProfile]:
    """Backend-independent profiles for every declared catalog prewarm
    key — no dispatch or lowering required, so the gate can build its
    golden cost table from an un-prewarmed engine in milliseconds."""
    dims = EngineDims.from_engine(engine)
    return {
        key: analytic_profile(key, dims)
        for key in engine.catalog.prewarm_keys()
    }


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HBMLedger:
    """Static per-rank HBM footprint of a serving engine, summed from the
    figures construction already knows — no device queries on the hot
    path. ``headroom_bytes`` may go negative: the engine is declared
    over budget (a real chip would OOM at allocation)."""

    budget_bytes: int
    param_bytes: int             # per-rank parameter shard
    pool_bytes: int              # KV pool per rank, scales included
    resident_bytes: int          # token/position/table resident arrays
    workspace_bytes: int         # largest program output+temp estimate
    footprint_bytes: int
    headroom_bytes: int

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def device_hbm_budget() -> int:
    """Per-device HBM budget. On a TPU, what the runtime reports as
    ``memory_stats()["bytes_limit"]`` — and an error when it reports none,
    because a budget guessed for a chip nobody asked is how a ledger lies.
    CPU hosts report no memory stats; they get the capacity of the
    :mod:`..flops` row the CPU tier borrows, so the ledger stays
    deterministic there."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        stats = dev.memory_stats() or {}
        if not stats.get("bytes_limit"):
            raise RuntimeError(
                f"{dev.device_kind}: memory_stats() reports no bytes_limit "
                f"(got {sorted(stats)}); pass PagedConfig.hbm_budget_bytes"
            )
        return int(stats["bytes_limit"])
    return flops_mod.chip_peaks().hbm_bytes


def hbm_ledger(
    engine: Any,
    profiles: Optional[Dict[tuple, CostProfile]] = None,
    budget_bytes: Optional[int] = None,
) -> HBMLedger:
    dims = EngineDims.from_engine(engine)
    resident = sum(
        int(getattr(arr, "nbytes", 0))
        for arr in (engine._d_tokens, engine._d_positions, engine._d_tables)
    )
    workspace = 0
    for p in (profiles or {}).values():
        if p.kind in COMPUTE_KINDS:
            workspace = max(workspace, p.output_bytes + p.temp_bytes)
    budget = int(budget_bytes) if budget_bytes else device_hbm_budget()
    pool = int(engine.metrics.pool_bytes_per_rank)
    footprint = dims.param_bytes_local + pool + resident + workspace
    return HBMLedger(
        budget_bytes=budget,
        param_bytes=dims.param_bytes_local,
        pool_bytes=pool,
        resident_bytes=resident,
        workspace_bytes=workspace,
        footprint_bytes=footprint,
        headroom_bytes=budget - footprint,
    )


# ---------------------------------------------------------------------------
# Cost table rendering (gate golden file scripts/graftcheck_costs.txt)
# ---------------------------------------------------------------------------


def cost_table_lines(profiles: Dict[tuple, CostProfile]) -> List[str]:
    """One stable line per profile: ``<formatted key> flops=<g>
    bytes=<g> arg=<d> src=<s>`` — sorted, backend-deterministic when the
    profiles are analytic. The gate's ``--costs-diff`` compares these the
    same way ``--catalog-diff`` compares manifest lines."""
    lines = []
    for p in profiles.values():
        lines.append(
            f"{p.label} flops={p.flops:.6g} bytes={p.bytes_accessed:.6g} "
            f"arg={p.argument_bytes} src={p.flops_source}"
        )
    return sorted(lines)
