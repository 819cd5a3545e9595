"""What the Jamba cell's own per-layer metrics read beside
``program_trace.py``: device seconds under the ``attn/ssm`` scopes inside given
programs, the decode dispatch records' ``rows``, ``state_lanes`` and
``state_slots_passed``, the prefill records' real rows, and the detail scopes'
seconds added to the traced line's breakdown. The scope walk, the dispatch
records and the "no prefill in this segment" rule are ``mla_trace.py``'s,
imported.

A program without these (no ``ssm`` scope, no ``state_lanes`` in a dispatch
record: the parent of PR 49) makes every function here return ``None``;
nothing raises. A program that has them but ran nothing under one in the
traced segment reads 0."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmarks import mla_trace, moe_trace

SSM = ("attn", "ssm")
DETAIL_PATHS = (
    SSM + ("conv",), SSM + ("params",), SSM + ("scan",), SSM + ("step",),
    ("attn", "sdpa"), ("attn", "kv_read"), ("attn", "kv_write"),
)
PREFILL = ("pctx", "psfx")

seconds_in = mla_trace.seconds_in
share = mla_trace.share
no_prefill_in_segment = mla_trace.no_prefill_in_segment
program_calls = moe_trace.program_calls


def named() -> bool:
    """Whether the program under test names the ``attn/ssm`` scopes at all."""
    return mla_trace.program_names(SSM)


def decode_records(result: Dict[str, Any]) -> Optional[List[Tuple[int, int, int]]]:
    """(rows, state_lanes, state_slots_passed) of each traced decode dispatch:
    the attention layers' live rows, the live lanes, the slots the pass moved."""
    if not named():
        return None
    records = [
        (int(a["rows"]), int(a["state_lanes"]), int(a["state_slots_passed"]))
        for a in mla_trace._dispatches(result, "decode")
        if "rows" in a and "state_lanes" in a and "state_slots_passed" in a
    ]
    return records or None


def prefill_rows(result: Dict[str, Any]) -> Optional[List[int]]:
    """Real rows (the bucket less its padding) of each traced prefill dispatch."""
    if not named():
        return None
    rows = [int(a["bucket"]) - int(a.get("pad", 0))
            for a in mla_trace._dispatches(result, "prefill") if "bucket" in a]
    return rows or None


def add_to_breakdown(result: Dict[str, Any]) -> None:
    """The mixers' scopes by name in the traced line's ``breakdown``: one
    ``scope <path>`` entry each among ``device_ops``, once."""
    reduced = result.get("reduced") or {}
    if "device_ops" not in reduced or result.get("ssm_breakdown_done"):
        return
    result["ssm_breakdown_done"] = True
    for path in DETAIL_PATHS:
        seconds = seconds_in(result, path)
        if seconds is not None:
            reduced["device_ops"].append(["scope " + "/".join(path), seconds])
