"""CPU rehearsal of the open-loop serving cell, end to end, and the refusal to
measure anything but a TPU."""

import os
import subprocess
import sys

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec


def test_chat_cell_end_to_end_metrics():
    line, _ = rehearse("mixtral-chat-steady", trace=0)
    names = check_line(line, spec.load_cell("mixtral-chat-steady"), trace=0)
    assert names == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert "breakdown" not in line


def test_chat_cell_traced_run_reports_what_it_can_read():
    line, out = rehearse("mixtral-chat-steady", trace=1)
    names = check_line(line, spec.load_cell("mixtral-chat-steady"), trace=1)
    # counters and host clocks exist on the CPU; device-trace metrics find no
    # device plane, return nothing, and are left out of the line
    assert {"gen_late_p90_ms", "ttft_p90_ms", "queue_wait_p50_ms", "prefix_hit_rate",
            "decode_batch_occupancy"} <= names
    assert "decode_step_dev_ms" not in names and "device_idle_share" not in names
    assert "left out" in out


def test_without_a_tpu_and_without_the_flag_it_refuses():
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload", "mixtral-chat-steady",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=spec.REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{") and '"metrics"' not in proc.stdout
