"""Compiled-program catalog: the declared bucket ladder and its manifest.

The NxD reference bounds its inference compile set with bucketed SPMD
models (``SPMDBucketModel``, PAPER.md §layer 9). The serving engine's
ProgramRecord registry (PR 9) made the compiled-program set *auditable*;
this module makes it *bounded*: a :class:`BucketLadder` declares every
shape the engine may pad a dispatch into (decode batch, prefill-chunk
buckets, kv-limit buckets, verify widths), and a :class:`CatalogManifest`
expands ladder × variant flags (gather / checked / quant) into the exact
set of legal ``_programs`` keys. The engine pads into the ladder at
dispatch time, ``PagedConfig.prewarm`` compiles the whole manifest before
traffic, and graftcheck enforces the contract statically:

- **GC007 (closed catalog)** — every registry key must be derivable from
  the manifest; an out-of-ladder compile is a finding naming the key and
  its nearest catalog bucket.
- **GC008 (steady-state compile freeze)** — after ``prewarm`` /
  ``mark_steady()``, growing the registry or re-lowering an existing key
  at new avals is a finding (the static twin of a recompile stall).

This keeps compile count O(ladder), not O(traffic): however heterogeneous
the admitted prompt lengths, chunk sizes and verify widths get, every
dispatch lands on one of the declared keys.

The powers-of-2 ladder helpers (``default_buckets`` / ``pick_bucket``)
are canonical HERE; ``inference/engine.py`` re-exports them for
back-compat (this module is dependency-light so both layers can share
one implementation without an import cycle).
"""

from __future__ import annotations

import dataclasses
from typing import Any, FrozenSet, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "BucketLadder",
    "CatalogManifest",
    "complete_ladder",
    "default_buckets",
    "format_key",
    "nearest_key",
    "pick_bucket",
    "validate_ladder",
]


def default_buckets(max_seq_len: int, min_bucket: int = 128) -> List[int]:
    """Powers-of-2 bucket ladder up to max_seq_len (reference
    autobucketing.py:6 generate_buckets)."""
    buckets = []
    b = min_bucket
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq_len)
    return buckets


def pick_bucket(buckets: Sequence[int], length: int) -> int:
    """Smallest bucket >= length (reference context-encode
    bucket-from-extent, autobucketing.py:62-124)."""
    for b in buckets:
        if b >= length:
            return b
    raise ValueError(f"length {length} exceeds largest bucket {buckets[-1]}")


def complete_ladder(
    buckets: Sequence[int], max_seq_len: int, dispatchable: Optional[int] = None
) -> List[int]:
    """Validated ascending ladder that ends at the rung holding the longest
    dispatch: every serving dispatch length <= ``dispatchable`` must route to
    SOME rung (the dense engine's ``_kv_bucket`` has the same
    clamp-to-full-cache fallback). ``dispatchable`` is ``max_seq_len`` unless
    the caller knows better — a chunked engine never hands prefill more than
    its chunk. The smallest declared rung that holds it ends the ladder,
    ``dispatchable`` itself is appended when the declared rungs top out
    under it, and rungs nothing can ask for are dropped."""
    out = [int(b) for b in buckets]
    if not out:
        raise ValueError("bucket ladder must not be empty")
    if any(b < 1 for b in out):
        raise ValueError(f"bucket ladder entries must be positive: {out}")
    if out != sorted(set(out)):
        raise ValueError(f"bucket ladder must be strictly ascending: {out}")
    if out[-1] > max_seq_len:
        raise ValueError(
            f"largest bucket {out[-1]} exceeds max_seq_len {max_seq_len}"
        )
    longest = min(int(dispatchable or max_seq_len), max_seq_len)
    top = next((b for b in out if b >= longest), longest)
    return [b for b in out if b < top] + [top]


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    """The declared shape ladder every serving dispatch pads into.

    ``prefill_buckets`` are padded prompt/chunk token counts (pctx/psfx
    programs), ``kv_buckets`` the kv_limit attention extents
    (psfx/pdecode/pverify), ``verify_t`` the speculative draft widths
    (one per configured ``spec_draft_tokens`` — the verify program's T is
    ``k + 1``). ``decode_batch`` is the fixed lane count B every batched
    program is traced at. Both bucket ladders end at ``max_seq_len``
    (see :func:`complete_ladder`)."""

    decode_batch: int
    max_seq_len: int
    prefill_buckets: Tuple[int, ...]
    kv_buckets: Tuple[int, ...]
    verify_t: Tuple[int, ...] = ()
    # fused-step row-width rungs (PagedConfig.fused_step): each rung is
    # the fixed query-row count T of a pmixed program packing
    # prefill-chunk, verify and decode rows into one grid — one rung per
    # engine today (max(prefill_chunk_tokens or 8, spec_k + 1))
    mixed_t: Tuple[int, ...] = ()

    def kv_bucket(self, needed: int) -> int:
        """Smallest kv rung covering ``needed`` rows, clamped to the full
        cache past the ladder top."""
        for b in self.kv_buckets:
            if b >= needed:
                return b
        return self.kv_buckets[-1]

    def prefill_bucket(self, length: int) -> int:
        return pick_bucket(self.prefill_buckets, max(length, 1))

    def suffix_pairs(self) -> List[Tuple[int, int]]:
        """Legal (prefill bucket, kv_limit) pairs for suffix prefill: a
        psfx dispatch at bucket ``b`` carries
        ``kv_limit = kv_bucket(min(cached + b, max_seq_len))`` with
        ``cached >= 1`` (cached == 0 routes to pctx), so exactly the kv
        rungs >= ``kv_bucket(min(1 + b, max_seq_len))`` are reachable."""
        out = []
        for b in self.prefill_buckets:
            lo = self.kv_bucket(min(1 + b, self.max_seq_len))
            out.extend((b, kv) for kv in self.kv_buckets if kv >= lo)
        return out


@dataclasses.dataclass(frozen=True)
class CatalogManifest:
    """Ladder × variant-flag expansion into the exact legal key set of
    the engine's ``_programs`` registry (the GC007 contract surface).

    ``gather_variants`` admits the degradation ladder's kernel-shed
    program twins (``PagedConfig.degrade_after_faults > 0``) as *legal*
    keys without prewarming them — GC006 forbids compiling gather twins
    on an engine that never degraded, so :meth:`prewarm_keys` is the
    gather-free subset. ``checked`` mirrors the engine's fixed
    ``_check_logits`` bit (checked and unchecked decode/verify traces are
    different programs; an engine only ever compiles one family)."""

    ladder: BucketLadder
    # SamplingConfig (frozen/hashable — rides inside keys), or the "lane"
    # string sentinel under fused on-device sampling
    sampling: Any
    quantized: bool = False
    checked: bool = False
    gather_variants: bool = False
    # PagedConfig.fused_step: prefill suffixes ride the pmixed grid, so
    # the psfx keys leave the universe entirely and the mixed_t × kv
    # ladder replaces the psfx suffix-pair product (the GC007 shrink)
    fused_step: bool = False
    # PagedConfig.spill_enabled: the tiered-KV host spill tier adds the
    # block_save/block_restore move programs to the universe (and only
    # then — registering them on a spill-free engine is a GC007 finding)
    spill: bool = False
    # PagedConfig.spec_tree: verify rungs become ptree keys (packed-tree
    # ancestor-masked verify) instead of pverify — same kv × k product,
    # so the manifest stays exactly as bounded as linear speculation's
    spec_tree: bool = False

    @classmethod
    def from_engine(cls, engine: Any) -> "CatalogManifest":
        """Derive the manifest a :class:`PagedServingEngine` (duck-typed)
        declares: its serving ladders, sampling config, quantization and
        checked bits, and whether the degradation ladder may mint
        gather twins."""
        spec_k = int(getattr(engine, "_spec_k", 0) or 0)
        mixed_t = int(getattr(engine, "_mixed_t", 0) or 0)
        ladder = BucketLadder(
            decode_batch=engine.engine.max_batch,
            max_seq_len=engine.engine.max_seq_len,
            prefill_buckets=tuple(engine._prefill_buckets),
            kv_buckets=tuple(engine._kv_buckets),
            verify_t=(spec_k,) if spec_k else (),
            mixed_t=(mixed_t,) if mixed_t else (),
        )
        return cls(
            ladder=ladder,
            # fused on-device sampling replaces the static SamplingConfig
            # key slot with the "lane" sentinel: per-lane params are
            # runtime arrays, so ONE program serves every sampling config
            sampling=(
                "lane" if getattr(engine, "_fused", False)
                else engine.gen.sampling
            ),
            quantized=bool(getattr(engine, "_kv_quantized", False)),
            checked=bool(getattr(engine, "_check_logits", False)),
            gather_variants=bool(engine.paged.degrade_after_faults),
            fused_step=bool(getattr(engine, "_fused_step", False)),
            spill=bool(getattr(engine, "_spill", False)),
            spec_tree=bool(getattr(engine, "_spec_tree", False)),
        )

    def _expand(self, gathers: Tuple[bool, ...]) -> List[tuple]:
        lad, cfg, chk = self.ladder, self.sampling, self.checked
        keys: List[tuple] = [
            ("copy_block", self.quantized),
            ("lane_set",),
            ("table_delta",),
        ]
        if self.spill:
            keys.append(("block_save", self.quantized))
            keys.append(("block_restore", self.quantized))
        for g in gathers:
            for b in lad.prefill_buckets:
                keys.append(("pctx", b, cfg, g))
            if not self.fused_step:
                # fused mode NEVER dispatches a suffix prefill: cached > 0
                # admissions route to the pmixed grid, so the psfx
                # suffix-pair product leaves the universe entirely
                for b, kv in lad.suffix_pairs():
                    keys.append(("psfx", b, kv, cfg, g))
            for kv in lad.kv_buckets:
                keys.append(("pdecode", cfg, kv, g, chk))
            verify_kind = "ptree" if self.spec_tree else "pverify"
            for k in lad.verify_t:
                for kv in lad.kv_buckets:
                    keys.append((verify_kind, kv, k, g, chk))
            for t in lad.mixed_t:
                for kv in lad.kv_buckets:
                    keys.append(("pmixed", t, kv, cfg, g, chk))
        return keys

    def keys(self) -> FrozenSet[tuple]:
        """Every key the engine may legally hold — the GC007 universe
        (gather twins included when the degradation ladder is armed)."""
        gathers = (False, True) if self.gather_variants else (False,)
        return frozenset(self._expand(gathers))

    def prewarm_keys(self) -> List[tuple]:
        """Deterministic compile order for :meth:`PagedServingEngine.
        prewarm`: the gather-free manifest (GC006 forbids gather twins on
        a never-degraded engine — the kernel-shed rung compiles its own
        on first use, exempted from the freeze)."""
        return self._expand((False,))

    def lines(self) -> List[str]:
        """Sorted human/golden-file rendering of :meth:`keys`."""
        return sorted(format_key(k) for k in self.keys())

    def describe(self) -> str:
        lad = self.ladder
        flags = [f for f, on in (
            ("quant", self.quantized), ("checked", self.checked),
            ("gather-variants", self.gather_variants),
        ) if on]
        if self.fused_step:
            flags.append("fused-step")
        if self.spill:
            flags.append("spill")
        if self.spec_tree:
            flags.append("spec-tree")
        return (
            f"B={lad.decode_batch} prefill={list(lad.prefill_buckets)} "
            f"kv={list(lad.kv_buckets)} verify_t={list(lad.verify_t)} "
            f"mixed_t={list(lad.mixed_t)} "
            f"cfg={_format_sampling(self.sampling)}"
            + (f" [{','.join(flags)}]" if flags else "")
            + f" -> {len(self.keys())} keys"
        )


def validate_ladder(model: Any, ladder: BucketLadder) -> List[str]:
    """Declaration-time warnings a prewarmed catalog should surface
    instead of discovering at first dispatch: a verify width past the
    Pallas kernel's linear bound, or a prefill chunk bucket that will pay
    the dense gather. Advisory (the gather paths are correct), returned
    as strings for the engine to log."""
    out = []
    path_of = getattr(model, "paged_dispatch_path", None)
    if path_of is None:
        return out
    for k in ladder.verify_t:
        if path_of(k + 1) != "kernel":
            out.append(
                f"verify_t={k} (T={k + 1}) exceeds the paged kernel's "
                "linear bound — every verify dispatch at this width takes "
                "the dense-gather path"
            )
    for t in ladder.mixed_t:
        if path_of(t) != "kernel":
            out.append(
                f"mixed_t={t} exceeds the paged kernel's linear bound — "
                "every fused mixed-mode dispatch takes the dense-gather "
                "path (shrink prefill_chunk_tokens / spec_draft_tokens)"
            )
    return out


# ---------------------------------------------------------------------------
# Key rendering (golden manifest file / GC007 findings)
# ---------------------------------------------------------------------------


def _format_sampling(cfg: Any) -> str:
    """Compact, comma-free SamplingConfig rendering for key strings
    (the fused-sampling "lane" sentinel passes through verbatim)."""
    if isinstance(cfg, str):
        return cfg
    if getattr(cfg, "greedy", False):
        return "greedy"
    bits = [f"T{cfg.temperature:g}"]
    if getattr(cfg, "top_k", 0):
        bits.append(f"k{cfg.top_k}")
    if getattr(cfg, "top_p", 1.0) < 1.0:
        bits.append(f"p{cfg.top_p:g}")
    return "-".join(bits)


def format_key(key: tuple) -> str:
    """Stable one-line rendering of a ``_programs`` registry key —
    ``kind[field=value,...,gather,checked]`` matching graftcheck's
    ``_registry_label`` house style, plus the sampling config (part of
    the key tuple but not of the record meta)."""
    kind = key[0]
    bits: List[str] = []
    gather = checked = False
    if kind == "pctx":
        _, b, cfg, gather = key
        bits = [f"bucket={b}", f"cfg={_format_sampling(cfg)}"]
    elif kind == "psfx":
        _, b, kv, cfg, gather = key
        bits = [f"bucket={b}", f"kv_limit={kv}", f"cfg={_format_sampling(cfg)}"]
    elif kind == "pdecode":
        _, cfg, kv, gather, checked = key
        bits = [f"kv_limit={kv}", f"cfg={_format_sampling(cfg)}"]
    elif kind in ("pverify", "ptree"):
        _, kv, k, gather, checked = key
        bits = [f"kv_limit={kv}", f"k={k}"]
    elif kind == "pmixed":
        _, t, kv, cfg, gather, checked = key
        bits = [f"t={t}", f"kv_limit={kv}", f"cfg={_format_sampling(cfg)}"]
    elif kind in ("copy_block", "block_save", "block_restore"):
        bits = [f"quantized={key[1]}"]
    else:  # lane_set / table_delta / future kinds: render fields raw
        bits = [str(f) for f in key[1:]]
    if gather:
        bits.append("gather")
    if checked:
        bits.append("checked")
    return str(kind) + (f"[{','.join(bits)}]" if bits else "")


def _key_distance(a: tuple, b: tuple) -> float:
    """Element-wise distance between two same-kind keys: numeric fields
    contribute their absolute difference, non-numeric fields a fixed
    penalty on mismatch — enough to rank 'nearest bucket' for GC007."""
    if a[0] != b[0] or len(a) != len(b):
        return float("inf")
    d = 0.0
    for x, y in zip(a[1:], b[1:]):
        num = isinstance(x, (int, float)) and not isinstance(x, bool)
        if num and isinstance(y, (int, float)) and not isinstance(y, bool):
            d += abs(float(x) - float(y))
        elif x != y:
            d += 1e6
    return d


def nearest_key(key: tuple, legal: Iterable[tuple]) -> Optional[str]:
    """Formatted nearest same-kind manifest key to an out-of-catalog
    ``key`` (the GC007 hint naming which bucket the dispatch should have
    padded into); None when the manifest holds no key of that kind."""
    best, best_d = None, float("inf")
    for cand in legal:
        d = _key_distance(key, cand)
        if d < best_d:
            best, best_d = cand, d
    return format_key(best) if best is not None else None


# ---------------------------------------------------------------------------
# Golden manifest file (scripts/graftcheck_catalog.txt)
# ---------------------------------------------------------------------------


def read_catalog_file(path: str) -> dict:
    """entry name -> sorted list of formatted key lines (comments and
    blank lines skipped). Same one-finding-per-line shape as the
    shardlint/graftcheck baselines, but exhaustive rather than
    grandfathering: the gate asserts byte-identity, not a subset."""
    import os

    out: dict = {}
    if not os.path.exists(path):
        return out
    with open(path, "r") as fh:
        for raw in fh:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split(None, 1)
            if len(parts) == 2:
                out.setdefault(parts[0], []).append(parts[1])
    for name in out:
        out[name] = sorted(out[name])
    return out


def write_catalog_file(path: str, entries: dict) -> None:
    """``entries``: entry name -> CatalogManifest (or a list of
    pre-formatted lines)."""
    with open(path, "w") as fh:
        fh.write(
            "# graftcheck golden catalog manifest: the exact legal "
            "compiled-program key set\n# per gate entry (GC007/GC008 "
            "contract). Regenerate with:\n#     python "
            "scripts/graftcheck_gate.py --write-catalog\n# A diff here is "
            "a deliberate ladder change and needs a commit rationale.\n"
            "# Format: <entry> <formatted program key>\n"
        )
        for name in sorted(entries):
            val = entries[name]
            lines = val.lines() if hasattr(val, "lines") else sorted(val)
            for line in lines:
                fh.write(f"{name} {line}\n")
