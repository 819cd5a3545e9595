"""What the longdoc cell's own per-layer metrics read beside
``program_trace.py``, ``moe_trace.py`` and ``mla_trace.py``: device seconds and
device operations under the multi-stream residual's scopes (``mhc/coeff``,
``mhc/sinkhorn``, ``mhc/mix``; ``models/xing.py``), the tracer's
``residual_row_bytes``. The quantities docqa's cell reads too (latent
attention, the experts, the step, the scheduler's) are read by the ``qa_``
readers, which list both cells: a segment without a prefill call reads 0
there (``mla_trace.no_prefill_in_segment``), and ``longdoc-batch``'s
``trace_s`` outlasts the longest stretch its lanes decode without one.

A program without the residual (no ``mhc`` in ``serving/tracing.py``
``DETAIL_SCOPES``, no ``residual_row_bytes`` in the ``setup`` record) makes
every function here return ``None``; nothing raises."""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

from benchmarks import mla_trace, program_trace

MHC = ("mhc",)
DETAIL_PATHS = (("mhc", "coeff"), ("mhc", "sinkhorn"), ("mhc", "mix"), ("attn", "q_latent"))


def names_residual() -> bool:
    """Whether the program under test names the residual's scopes at all."""
    return mla_trace.program_names(MHC)


def under(result: Dict[str, Any], path: Sequence[str],
          programs: Optional[Sequence[str]] = None) -> Optional[Tuple[float, int]]:
    """(device seconds, device operations) — device 0, the traced window — of
    the ops whose ``op_name`` path holds ``path``'s names as whole parts in
    that order, inside the programs ``programs`` (any, if None)."""
    trace = program_trace.loaded(result)
    if trace is None:
        return None
    dev = trace["devices"][0]
    roots = program_trace.program_scope_by_id(dev.ops)
    lo, hi = trace["window"]
    seconds, count = 0.0, 0
    for op in dev.ops:
        if not program_trace._counted(op) or op.end <= lo or op.start >= hi:
            continue
        if programs is not None and roots.get(op.program_id) not in programs:
            continue
        parts = iter(inner for _, inner in program_trace.segments(op.tf_op))
        if all(name in parts for name in path):      # in order: `in` consumes the iterator
            seconds += op.dur
            count += 1
    return seconds, count


def add_to_breakdown(result: Dict[str, Any]) -> None:
    """The residual's scopes and the query latent's by name in the traced
    line's ``breakdown``, beside ``mla_trace.add_to_breakdown``'s: one
    ``scope <path>`` entry each among ``device_ops`` (seconds, device 0), once."""
    reduced = result.get("reduced") or {}
    if "device_ops" not in reduced or result.get("residual_breakdown_done"):
        return
    result["residual_breakdown_done"] = True
    for path in DETAIL_PATHS:
        got = under(result, path)
        if got is not None:
            reduced["device_ops"].append(["scope " + "/".join(path), got[0]])

