"""What the MiniCPM-SALA cell's own per-layer metrics read beside
``program_trace.py``: device seconds under the ``attn/sparse`` and
``attn/lightning`` scopes inside given programs, the decode dispatch records'
``sparse_rows_cached`` / ``sparse_rows_read`` / ``sparse_blocks_forced`` and
their live lanes, the prefill records' real rows, and the detail scopes'
seconds added to the traced line's breakdown. The scope walk, the dispatch
records and the "no prefill in this segment" rule are ``mla_trace.py``'s,
imported.

A program without these (no ``sparse`` scope, no ``sparse_rows_read`` in a
dispatch record: the parent of PR 52) makes every function here return
``None``; nothing raises. A program that has them but ran nothing under one in
the traced segment reads 0."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmarks import mla_trace, moe_trace

SPARSE = ("attn", "sparse")
LIGHTNING = ("attn", "lightning")
DETAIL_PATHS = (
    SPARSE + ("pool_keys",), SPARSE + ("select",), SPARSE + ("read",),
    LIGHTNING + ("chunk",), LIGHTNING + ("step",), LIGHTNING + ("gate_norm",),
    ("attn", "kv_write"), ("attn", "qk_norm"),
)
PREFILL = ("pctx", "psfx")

seconds_in = mla_trace.seconds_in
no_prefill_in_segment = mla_trace.no_prefill_in_segment
program_calls = moe_trace.program_calls


def named() -> bool:
    """Whether the program under test names both mixers' scopes at all."""
    return mla_trace.program_names(SPARSE) and mla_trace.program_names(LIGHTNING)


def decode_records(result: Dict[str, Any]) -> Optional[List[Tuple[int, int, int, int]]]:
    """(live lanes, rows cached, rows read, blocks forced) of each traced
    decode dispatch, the last three summed over its live lanes a layer."""
    if not named():
        return None
    records = [
        (int(a["state_lanes"]), int(a["sparse_rows_cached"]), int(a["sparse_rows_read"]),
         int(a["sparse_blocks_forced"]))
        for a in mla_trace._dispatches(result, "decode")
        if "sparse_rows_read" in a and "sparse_rows_cached" in a and "state_lanes" in a
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
    if "device_ops" not in reduced or result.get("sala_breakdown_done"):
        return
    result["sala_breakdown_done"] = True
    for path in DETAIL_PATHS:
        seconds = seconds_in(result, path)
        if seconds is not None:
            reduced["device_ops"].append(["scope " + "/".join(path), seconds])
