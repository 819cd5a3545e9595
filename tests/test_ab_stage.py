"""Smoke test of the A/B stage script (scripts/ab_stage.py) on the CPU
plumbing tier."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "which,timeout",
    [
        ("head", 180),
        # tier-1 budget: the ring leg doubles the head leg's coverage of
        # the stage driver; it rides in the slow tier
        pytest.param("ring", 300, marks=pytest.mark.slow),
    ],
)
def test_ab_stage_smoke(which, timeout):
    """The A/B stage scripts run end-to-end on the CPU plumbing tier and
    emit one parseable JSON record with the comparison fields."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ab_stage.py"),
         "--which", which, "--cpu", "--quick", "--iters", "1"],
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    if which == "head":
        assert rec["ab"] == "head_sequence_split"
        assert rec["ici_unmeasured"] is True
        assert rec["split_fwdbwd_ms"] > 0 and rec["unsplit_fwdbwd_ms"] > 0
    else:
        assert rec["ab"] == "ring_zigzag_vs_contiguous"
        row = rec["rows"][0]
        assert row["critical_contiguous_fwdbwd_ms"] > 0
        assert row["critical_zigzag_fwdbwd_ms"] > 0
