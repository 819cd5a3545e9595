"""CPU rehearsal of the MiniCPM-SALA long-context cell through the benchmark's
one command: the end-to-end line, and the traced line with the metrics that
read the program's counters; what the two new device-trace readers return
where there is no trace, no prefill call, or no such scope, and what they make
of the dispatch records' counters; and the check's planted faults through
their tool at the rehearsal's size."""

import json
import os
import subprocess
import sys

import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELL = "sala-longctx-steady"
HOST = {"gen_late_p90_ms", "ttft_p90_ms", "tpot_p90_ms", "queue_wait_p50_ms",
        "admit_to_first_token_p50_ms", "door_pre_submit_p50_ms", "door_first_write_p50_ms",
        "step_host_self_ms"}
COUNTERS = {"decode_batch_occupancy", "lookahead_step_share"}
NEW = ("sala_sparse_decode_roofline", "sala_lightning_prefill_roofline")


def test_longctx_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert '"plain_pool_is_own": true' in out and '"rows": 84' in out     # 80 + 4: contexts that drop blocks


def test_longctx_cell_traced_run_reads_the_counters():
    line, out = rehearse(CELL, trace=1, seed=3_000_000_001)       # the driver's seeds pass 2**31
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert HOST <= names <= HOST | COUNTERS
    every = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert len(every) == 17 and set(NEW) <= every
    for name in every - names:        # device-trace metrics find no device plane on the host
        assert f"note: {name}: nothing to read, left out" in out


@pytest.mark.parametrize("metric", NEW)
def test_readers_return_nothing_without_a_trace_and_zero_without_a_prefill(metric, monkeypatch):
    from benchmarks import peaks, serving_trace

    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, True, max_seq_len=128)
    result = {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
              "profile": None, "reduced": None}
    read = spec.load_metric("layer_metrics", metric, cell.root)
    assert read(dict(result)) is None                      # no device trace, no dispatch record: left out
    if "prefill" in metric:
        decode_only = {"decode": [(0.012, 60)] * 90, "prefill": []}
        monkeypatch.setattr(serving_trace, "classify", lambda r: (decode_only, ""))
        traced = dict(result)
        assert read(traced) == 0.0 and any("no prefill call" in n for n in traced["notes"])
    # a program that does not name the scopes (the parent's) has nothing to read
    from neuronx_distributed_llama3_2_tpu.serving import tracing
    monkeypatch.setattr(tracing, "DETAIL_SCOPES", {"attn": ("qk_norm",)})
    assert read(dict(result)) is None


def test_the_dispatch_records_feed_both_rooflines(monkeypatch):
    from benchmarks import peaks, sala_trace

    def step(lanes, cached, read, forced, **extra):
        return {"step": 0, "events": [("X", "dispatch", 0.0, 0.1, {
            "rows": cached, "state_lanes": lanes, "state_slots_passed": 24, "lanes": lanes,
            "sparse_rows_cached": cached, "sparse_rows_read": read, "sparse_blocks_forced": forced, **extra})]}

    prefill = {"step": 1, "events": [("X", "prefill", 0.0, 0.1, {"bucket": 512, "kv_bucket": 8192, "pad": 12})]}
    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, False, max_seq_len=33280)
    result = {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
              "profile": {"engine_steps": [step(10, 177000, 41200, 340), step(12, 212400, 49440, 408), prefill]}}
    assert sala_trace.decode_records(result) == [(10, 177000, 41200, 340), (12, 212400, 49440, 408)]
    assert sala_trace.prefill_rows(result) == [500]
    monkeypatch.setattr(sala_trace, "program_calls", lambda r, kinds: 2)
    monkeypatch.setattr(sala_trace, "add_to_breakdown", lambda r: None)
    monkeypatch.setattr(sala_trace, "seconds_in", lambda r, path, programs=None: 0.004)
    decode = spec.load_metric("layer_metrics", NEW[0])(result)
    # 11 lanes of 17,700 rows: 2 layers x (1,105 kernels + 2 x 4,068 rows) x 512 B a lane a step, two steps in 4 ms
    need = 2 * 11 * 2 * (1105 * 512 + 2 * (63 * 64 + 36) * 512)
    assert decode == pytest.approx(100.0 * need / 0.004 / 819e9) and 0 < decode < 100
    assert any("read 4120 rows a lane a layer" in n for n in result["notes"])
    chunk = spec.load_metric("layer_metrics", NEW[1])(result)
    flops = 6 * 32 * 1000 * (2 * 128 * 501 + 4 * 128 * 128)
    assert chunk == pytest.approx(100.0 * flops / 0.004 / 197e12) and 0 < chunk < 100
    # the parent's records carry no sparse counters: nothing to read
    bare = {"step": 0, "events": [("X", "dispatch", 0.0, 0.1, {"rows": 900, "lanes": 30, "state_lanes": 30})]}
    assert sala_trace.decode_records({"profile": {"engine_steps": [bare]}}) is None


def test_the_variant_tool_fails_the_check_at_the_rehearsals_size():
    """Two of the variants through the tool itself, planted together in one
    engine (the others, and every row's comparison, are
    ``tests/test_minicpm_sala_serving.py``'s): a state pool in bfloat16 fails
    by the cache's tolerance, a decay left out by the rows'."""
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "tools", "check_sala_variant.py"), CELL,
         "--seed", "5", "--rehearse-on-cpu", "1", "--set", "cache_dtype=bfloat16", "--fault", "no_decay"],
        capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    checked = json.loads(proc.stdout.strip().splitlines()[-1].split(": ", 1)[1])
    assert checked["ok"] is False
    assert checked["cache"]["p50"] > checked["cache"]["tolerance"] and not checked["cache"]["plain_pool_is_own"]
    assert checked["all_rows"]["p50"] > checked["tolerance"]
