"""Running the benchmark's command as the driver does — a fresh process, the
last line of stdout — but as the CPU rehearsal."""

import json
import os
import subprocess
import sys

from benchmarks import spec
from benchmarks.run import REHEARSAL_PREFIX


def rehearse(workload, devices=1, trace=0, seed=3, seconds=2, extra=(), cwd=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse-on-cpu", str(devices), *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=cwd or spec.REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "REHEARSAL ON CPU" in proc.stdout
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def check_line(line, cell, trace):
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"          # the host, named as such
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    # no number under a metric's own name
    assert line["metrics"] and all(k.startswith(REHEARSAL_PREFIX) for k in line["metrics"])
    names = {k[len(REHEARSAL_PREFIX):] for k in line["metrics"]}
    rows = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in rows}
    assert names <= set(units)
    if not trace:
        assert names == set(units)
    for k, v in line["metrics"].items():
        assert v["unit"] == units[k[len(REHEARSAL_PREFIX):]] and v["value"] == v["value"]
    return names
