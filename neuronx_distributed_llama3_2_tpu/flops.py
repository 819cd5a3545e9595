"""Shared model-FLOP arithmetic: ONE formula for train- and serve-side MFU.

Historically the training estimator lived in ``trainer/metrics.py``
(consumed by ``bench.py`` and ``scripts/mfu_sweep.py``) while the serving
engine had no FLOP model at all. graftmeter (docs/serving.md "Cost
accounting & SLOs") needs a serve-side estimate for its analytic
CostProfile fallback, so the formula moves here and both sides import it
— train-side MFU and the serving roofline can never drift apart again.

The model: a forward pass costs ``2·N`` matmul FLOPs per token plus the
attention term ``4·L·H·K`` at context length ``K`` (two batched matmuls,
QKᵀ and attn·V, each ``2·H·K`` per layer). Training multiplies by 3 for
the backward pass, recovering the classic ``6·N + 12·L·H·S`` — exactly
the expression ``trainer/metrics.py`` always used, verified drift-free
when this module was factored out.

Peak figures come from :data:`CHIP_PEAKS`, one row per ``device_kind``
with its source; :func:`chip_peaks` is the only way to read one.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks that MFU and the serving roofline divide by."""

    bf16_flops: float      # FLOP/s, bf16 matmul
    hbm_bytes: int         # HBM capacity
    hbm_bw: float          # bytes/s
    source: str


# keyed by ``jax.devices()[0].device_kind``
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12,
        hbm_bytes=16 * 2**30,
        hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e" system architecture: '
               "197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip",
    ),
}

# the row the CPU tier borrows: tests and analyzers price programs against a
# fixed chip so their analytic cost tables stay deterministic on a host that
# has no peaks of its own. Never the row of a run that produces device numbers.
CPU_BORROWED_KIND = "TPU v5 lite"


def chip_peaks(device_kind: Optional[str] = None) -> ChipPeaks:
    """The :data:`CHIP_PEAKS` row for ``device_kind`` (default: the first
    device of this process). A TPU kind that is not in the table is an
    error, not a default; a CPU process gets :data:`CPU_BORROWED_KIND`."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            device_kind = CPU_BORROWED_KIND
        elif dev.platform == "tpu":
            device_kind = dev.device_kind
        else:
            raise ValueError(
                f"no peaks for platform {dev.platform!r} ({dev.device_kind})"
            )
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"device kind {device_kind!r} is not in flops.CHIP_PEAKS "
            f"(known: {sorted(CHIP_PEAKS)}); add its published peaks with "
            "their source before normalising numbers by them"
        ) from None


def model_flops_per_token(
    num_params: int,
    num_layers: int,
    hidden_size: int,
    context_len: int,
    backward: bool = False,
) -> float:
    """Per-token model FLOPs at attention context ``context_len``:
    ``2·N + 4·L·H·K`` forward, ×3 with the backward pass."""
    fwd = 2 * num_params + 4 * num_layers * hidden_size * context_len
    return 3.0 * fwd if backward else float(fwd)


def train_flops_per_token(
    num_params: int, num_layers: int, hidden_size: int, seq_len: int
) -> float:
    """Per-token training FLOPs (``6·N + 12·L·H·S``). Single source of
    truth for MFU and bench targets — re-exported by trainer/metrics.py."""
    return model_flops_per_token(
        num_params, num_layers, hidden_size, seq_len, backward=True
    )


def decode_flops_per_token(
    num_params: int, num_layers: int, hidden_size: int, kv_len: int
) -> float:
    """Per-token decode FLOPs at kv context ``kv_len`` — the serving-side
    twin of :func:`train_flops_per_token` (forward only)."""
    return model_flops_per_token(num_params, num_layers, hidden_size, kv_len)


def mfu(
    tokens_per_sec: float,
    num_params: int,
    num_layers: int,
    hidden_size: int,
    seq_len: int,
    peak_flops_per_chip: float,
    num_chips: int = 1,
) -> float:
    """Model FLOPs utilization (training convention)."""
    achieved = tokens_per_sec * train_flops_per_token(
        num_params, num_layers, hidden_size, seq_len
    )
    return achieved / (peak_flops_per_chip * num_chips)
