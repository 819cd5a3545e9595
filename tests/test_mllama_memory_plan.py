"""Smoke test of the Mllama 11B memory-plan script
(scripts/mllama_memory_plan.py)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mllama_memory_plan_skip_measure_smoke():
    """The 11B memory-plan script's exact accounting path runs and emits
    the static byte plan (VERDICT r4 #3; the full measured path is the
    docs/mllama_memory_plan.md deliverable)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mllama_memory_plan.py"),
         "--skip-measure"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    exact = rec["exact"]
    assert exact["mesh"] == {"tp": 8, "dp": 8}
    assert exact["n_params_B"] > 9  # the 11B model, not a stub
    for k in ("bf16_params_GB_per_chip", "zero1_master_fp32_GB_per_chip",
              "zero1_moments_fp32_GB_per_chip", "grads_GB_per_chip",
              "static_total_GB_per_chip"):
        assert exact[k] > 0
    assert exact["static_total_GB_per_chip"] < rec["hbm_per_chip_GB"]
