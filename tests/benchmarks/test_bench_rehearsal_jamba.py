"""CPU rehearsal of the Jamba bursty-chat cell through the benchmark's one
command: the end-to-end line, and the traced line with the metrics that read
the program's counters; what the device-trace readers return where there is no
trace, no prefill call, or no such scope; and the check's planted faults
through their tool at the rehearsal's size."""

import json
import os
import subprocess
import sys

import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELL = "jamba-smallchat-bursty"
COUNTERS = {"gen_late_p90_ms", "ttft_p90_ms", "tpot_p90_ms", "queue_wait_p50_ms",
            "admit_to_first_token_p50_ms", "door_pre_submit_p50_ms", "door_first_write_p50_ms",
            "step_host_self_ms", "decode_batch_occupancy", "lookahead_step_share", "ssm_state_live_share"}


def test_smallchat_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert '"plain_pool_is_own": true' in out


def test_smallchat_cell_traced_run_reads_the_counters():
    line, out = rehearse(CELL, trace=1, seed=3_000_000_001)       # the driver's seeds pass 2**31
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert names == COUNTERS
    every = {m["name"] for m in spec.load_cell(CELL).per_layer}
    for name in every - COUNTERS:        # device-trace metrics find no device plane on the host
        assert f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    # an open loop at partial occupancy: the pass moves every lane's slot, live or not
    assert 0.0 < values["ssm_state_live_share"] <= 100.0
    assert values["ssm_state_live_share"] == pytest.approx(values["decode_batch_occupancy"], rel=0.2)


@pytest.mark.parametrize("metric", ["ssm_mixer_dev_share", "ssm_scan_prefill_roofline",
                                    "ssm_step_decode_roofline", "ssm_pdecode_roofline",
                                    "ssm_state_live_share"])
def test_readers_return_nothing_without_a_trace_and_zero_without_a_prefill(metric, monkeypatch):
    from benchmarks import moe_trace, peaks, serving_trace

    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, True, max_seq_len=64)
    result = {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
              "profile": None, "reduced": None}
    read = spec.load_metric("layer_metrics", metric, cell.root)
    assert read(dict(result)) is None                      # no device trace, no dispatch record: left out
    if metric in ("ssm_scan_prefill_roofline", "ssm_mixer_dev_share"):
        decode_only = {"decode": [(0.012, 60)] * 90, "prefill": []}
        monkeypatch.setattr(serving_trace, "classify", lambda r: (decode_only, ""))
        monkeypatch.setattr(moe_trace, "path_seconds", lambda r, path: (0.0, 2.7))
        traced = dict(result)
        assert read(traced) == 0.0
        if "prefill" in metric:
            assert any("no prefill call" in n for n in traced["notes"])
    # a program that does not name the scopes (the parent's) has nothing to read
    from neuronx_distributed_llama3_2_tpu.serving import tracing
    monkeypatch.setattr(tracing, "DETAIL_SCOPES", {"attn": ("qk_norm",)})
    assert read(dict(result)) is None


def test_the_decode_records_feed_the_two_counters():
    from benchmarks import ssm_trace

    def step(rows, lanes, slots, **extra):
        return {"step": 0, "events": [("X", "dispatch", 0.0, 0.1, {
            "rows": rows, "state_lanes": lanes, "state_slots_passed": slots, "lanes": lanes, **extra})]}

    result = {"kind": "serving", "profile": {"engine_steps": [step(900, 30, 128), step(1200, 34, 128)]}}
    assert ssm_trace.decode_records(result) == [(900, 30, 128), (1200, 34, 128)]
    read = spec.load_metric("layer_metrics", "ssm_state_live_share")
    assert read(result) == pytest.approx(100.0 * 64 / 256)
    prefill = {"step": 1, "events": [("X", "prefill", 0.0, 0.1, {"bucket": 512, "kv_bucket": 0, "pad": 12})]}
    assert ssm_trace.prefill_rows({"profile": {"engine_steps": [prefill]}}) == [500]
    # the parent's records carry no state_lanes: nothing to read
    bare = {"step": 0, "events": [("X", "dispatch", 0.0, 0.1, {"rows": 900, "lanes": 30})]}
    assert ssm_trace.decode_records({"profile": {"engine_steps": [bare]}}) is None


def test_the_variant_tool_fails_the_check_at_the_rehearsals_size():
    """Two of the variants through the tool itself, planted together in one
    engine (the others, and every row's comparison, are
    ``tests/test_jamba_serving.py``'s): a state pool in bfloat16 fails by the
    cache's tolerance, an inner norm left out by the rows'. The rehearsal names
    the ``interpret`` kernel mode, so the chunks go through the scan kernel."""
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "tools", "check_ssm_variant.py"), CELL,
         "--seed", "5", "--rehearse-on-cpu", "1", "--set", "cache_dtype=bfloat16", "--fault", "no_b_norm"],
        capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    checked = json.loads(proc.stdout.strip().splitlines()[-1].split(": ", 1)[1])
    assert checked["ok"] is False
    assert checked["cache"]["p50"] > checked["cache"]["tolerance"] and not checked["cache"]["plain_pool_is_own"]
    assert checked["all_rows"]["p50"] > checked["tolerance"]
