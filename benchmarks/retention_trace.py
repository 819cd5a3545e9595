"""What the Brumby cell's own per-layer metrics read beside
``program_trace.py``: device seconds under the retention scopes inside given
programs, the decode dispatch records' ``rows`` (live lanes, where the cache is
a state a lane), the prefill records' buckets, the ``setup`` record's
``state_bytes_per_lane``, and the detail scopes' seconds added to the traced
line's breakdown. The scope walk, the dispatch records and the "no prefill in
this segment" rule are ``mla_trace.py``'s, imported.

A program without these (no ``retention`` scope, no ``state_bytes_per_lane``)
makes every function here return ``None``; nothing raises. A program that has
them but ran nothing under one in the traced segment reads 0."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmarks import mla_trace, program_trace

RETENTION = ("attn", "retention")
DETAIL_PATHS = (
    ("attn", "qkv"), ("attn", "qk_norm"), ("attn", "gate"), RETENTION + ("expand",),
    RETENTION + ("chunk",), RETENTION + ("step",), ("attn", "o_proj"),
)
PREFILL = ("pctx", "psfx")

seconds_in = mla_trace.seconds_in
share = mla_trace.share
no_prefill_in_segment = mla_trace.no_prefill_in_segment


def named(path: Sequence[str] = RETENTION) -> bool:
    """Whether the program under test names the retention scopes at all."""
    return mla_trace.program_names(path)


def live_lanes(result: Dict[str, Any]) -> Optional[List[int]]:
    """States each traced decode dispatch had to move: its record's ``rows``."""
    return mla_trace.decode_rows(result) if named() else None


def prefill_buckets(result: Dict[str, Any]) -> Optional[List[int]]:
    calls = mla_trace.prefill_calls(result) if named() else None
    return [bucket for bucket, _ in calls] if calls else None


def state_bytes_per_lane(result: Dict[str, Any]) -> Optional[int]:
    tl = program_trace.timeline(result) if result.get("kind") == "serving" else None
    value = (tl or {}).get("setup", {}).get("state_bytes_per_lane")
    return int(value) if value else None


def add_to_breakdown(result: Dict[str, Any]) -> None:
    """The attention block's scopes by name in the traced line's
    ``breakdown``: one ``scope <path>`` entry each among ``device_ops``."""
    reduced = result.get("reduced") or {}
    if "device_ops" not in reduced or result.get("retention_breakdown_done"):
        return
    result["retention_breakdown_done"] = True
    for path in DETAIL_PATHS:
        seconds = seconds_in(result, path)
        if seconds is not None:
            reduced["device_ops"].append(["scope " + "/".join(path), seconds])
