"""What the Laguna cell's own per-layer metrics read beside
``program_trace.py``, ``moe_trace.py`` and ``mla_trace.py``: device seconds
under ``attn/full`` and ``attn/window`` (the detail scopes around a layer's
attention block, by its kind), the ``setup`` record's ``cache_kinds`` and
``window_ring_rows``, and the decode dispatch records' ``rows`` and
``window_rows`` (which the program writes since PR 42).

A program without these (no ``full`` / ``window`` detail scope, no
``cache_kinds`` in the setup record, no ``window_rows`` in a dispatch record)
makes every function here return ``None``; nothing raises. A program that has
them but ran no such program in the traced segment reads 0, with a note."""

from __future__ import annotations

import statistics
import types
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks import arith_window, mla_trace, moe_trace, program_trace

KINDS = ("full", "window")


def names_kinds() -> bool:
    """Whether the program under test names the two kinds' detail scopes."""
    return all(mla_trace.program_names(("attn", kind)) for kind in KINDS)


def kind_share(result: Dict[str, Any], kind: str) -> Optional[float]:
    """Percent of device busy time under ``attn/<kind>``."""
    return mla_trace.share(result, ("attn", kind))


def cache_kinds(result: Dict[str, Any]) -> Optional[Dict[str, dict]]:
    """The ``setup`` record's ``cache_kinds`` with both kinds in it: a kind ->
    ``layers``, ``rows_per_lane`` (null: the whole context), ``row_bytes``."""
    tl = program_trace.timeline(result) if result.get("kind") == "serving" else None
    kinds = (tl or {}).get("setup", {}).get("cache_kinds")
    if not kinds or not set(KINDS) <= set(kinds):
        return None
    return kinds


def decode_records(result: Dict[str, Any]) -> Optional[List[Tuple[int, int, int]]]:
    """(lanes, rows, window rows) of each traced decode dispatch that says all
    three; [] where the segment dispatched no decode, None where the program
    writes no ``window_rows``."""
    prof = result.get("profile") or {}
    if not prof.get("engine_steps") or cache_kinds(result) is None:
        return None
    records = mla_trace._dispatches(result, "decode")
    return [(int(a["lanes"]), int(a["rows"]), int(a["window_rows"]))
            for a in records if "window_rows" in a and "rows" in a]


def decode_calls(result: Dict[str, Any]) -> Optional[Tuple[int, List[float]]]:
    """(executions of ``pdecode`` in the traced window, their device ms);
    (0, []) where there is a device trace and no ``pdecode`` ran in it, None
    without a device trace."""
    if result.get("kind") != "serving" or program_trace.loaded(result) is None:
        return None
    runs = program_trace.program_run_ms(result, "pdecode") or []
    if not runs:
        program_trace._note(result, "no pdecode call in the traced segment: the decode metrics read 0")
    return len(runs), runs


def mean_decode_record(result: Dict[str, Any]) -> Optional[Tuple[float, float, float]]:
    """Mean (lanes, rows, window rows) over the traced decode dispatches."""
    records = decode_records(result)
    if not records:
        return None
    return tuple(statistics.fmean(col) for col in zip(*records))


def kind_decode_roofline(result: Dict[str, Any], kind: str) -> Optional[float]:
    """Percent of the chip's memory bandwidth at which the ``pdecode`` calls of
    the traced segment read the rows their live lanes *need* from ``kind``'s
    layers, over the device time under ``attn/<kind>`` in ``pdecode``."""
    if result.get("peaks") is None or not names_kinds():
        return None
    got = decode_calls(result)
    if got is None:
        return None
    calls, _ = got
    seconds = mla_trace.seconds_in(result, ("attn", kind), ("pdecode",))
    mean = mean_decode_record(result)
    if not calls or not seconds or mean is None:
        return 0.0 if decode_records(result) is not None else None
    c = result["model_cfg"]
    rows = mean[1] if kind == "full" else mean[2]
    need = calls * arith_window.decode_needed_row_bytes(
        rows, c.layers_of(kind), c.num_kv_heads, c.head_dim, itemsize=_itemsize(c))
    program_trace._note(
        result, f"{kind} attention in decode: {calls} calls over {rows:.0f} visible rows need "
        f"{need / 1e9:.3f} GB of cache rows, {seconds:.3f} s under attn/{kind}")
    return 100.0 * need / seconds / result["peaks"].hbm_bytes_per_s


def _itemsize(cfg) -> int:
    return np.dtype(cfg.dtype).itemsize


def expert_view(cfg):
    """The model config as ``moe_trace.expert_copy_seconds`` reads it: the
    experts' own width where the dense layers' differs."""
    return types.SimpleNamespace(
        num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.moe_intermediate_size)


def expert_decode_seconds(result: Dict[str, Any], programs: Sequence[str] = ("pdecode",)) -> Optional[float]:
    return moe_trace.expert_seconds({**result, "model_cfg": expert_view(result["model_cfg"])}, programs)
