"""CPU rehearsal of the SmallThinker long-chat cell through the benchmark's
one command: the end-to-end line, the traced line with the readers the cell
joined (it brought none of its own: ``per_layer`` is full), how the shared
scope readers book this model's paths — a layer's ``moe/router`` ahead of its
``attn``, the ring gather under ``attn/window`` — and the planted-fault tool at
the rehearsal's size."""

import json
import os
import subprocess
import sys

import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import program_trace, spec

CELL = "smallthinker-longchat-steady"
HOST = {"gen_late_p90_ms", "ttft_p90_ms", "tpot_p90_ms", "queue_wait_p50_ms",
        "admit_to_first_token_p50_ms", "door_pre_submit_p50_ms", "door_first_write_p50_ms",
        "step_host_self_ms"}
COUNTERS = {"decode_batch_occupancy", "lookahead_step_share"}
DEVICE = {"decode_step_dev_ms", "pdecode_dev_p50_ms", "device_idle_share", "idle_in_step_share",
          "idle_between_steps_share", "moe_dev_share", "kv_dev_share"}


def test_the_cell_is_the_issues():
    cell = spec.load_cell(CELL)
    assert cell.config["family"] == "smallthinker" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == HOST | COUNTERS | DEVICE
    t, e = cell.traffic, cell.traffic["engine"]
    assert (t["kind"], t["schedule_seed"], t["output_tokens"]) == ("open_poisson", 57, 512)
    assert t["prompt_tokens"] == {"dist": "log_uniform", "low": 2048, "high": 12288}
    assert t["limits"] == {"ttft_ms": 4000, "tpot_ms": 60, "attainment": 0.9}
    assert (e["lanes"], e["block_size"], e["max_seq_len"], e["pool_blocks"]) == (32, 16, 12800, 32 * 800 + 512)
    assert e["kv_buckets"] == [2048, 4096, 8192, 12800] and e["prefill_buckets"] == [128, 512]
    # the check runs past the window and the 4,608-row ring, in whole chunks
    assert t["check"]["prompt_tokens"] >= 5632 and t["check"]["prompt_tokens"] % e["prefill_chunk_tokens"] == 0
    cfg = cell.config
    assert (cfg["hidden_size"], cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"],
            cfg["moe_num_active_primary_experts"], cfg["vocab_size"], cfg["sliding_window_size"]) == (
        2560, 768, 64, 6, 151936, 4096)
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1] and cfg["num_hidden_layers"] == 4
    assert set(cfg["reduced"]) == {"num_hidden_layers", "sliding_window_layout", "rope_layout"}
    model = spec.load_family("smallthinker").model_config(cfg, False, max_seq_len=e["max_seq_len"])
    assert model.num_heads // model.num_kv_heads == 7 and model.moe_config().activation == "relu"


def test_longchat_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
    assert '"plain_pool_is_own": true' in out and '"rows": 44' in out      # 40 + 4: past the window of 8, the ring of 24


def test_longchat_cell_traced_run_reads_the_counters():
    line, out = rehearse(CELL, trace=1, seed=3_000_000_057)       # the driver's seeds pass 2**31
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert HOST <= names <= HOST | COUNTERS
    for name in DEVICE:                # device-trace metrics find no device plane on the host
        assert f"note: {name}: nothing to read, left out" in out


@pytest.mark.parametrize("path,booked", [
    # the early route: a layer opens ``moe`` ahead of ``attn``
    ("jit(fn)/pdecode/while/body/moe/router/dot_general:", ("pdecode", "moe", "moe/router")),
    ("jit(fn)/pdecode/while/body/attn/window/kv_read/gather:", ("pdecode", "attn", "attn/kv_read")),
    ("jit(fn)/pdecode/while/body/attn/full/sdpa/paged_decode_walk/pallas_call:", ("pdecode", "attn", "attn/sdpa")),
    ("jit(fn)/psfx/while/body/attn/window/rope/mul:", ("psfx", "attn", "attn/rope")),
    ("jit(fn)/psfx/while/body/moe/experts/all/dot_general:", ("psfx", "moe", "moe/experts")),
])
def test_the_shared_readers_book_this_models_scopes(path, booked):
    assert program_trace.scopes_of(path) == booked


def test_the_variant_tool_fails_the_check_at_the_rehearsals_size():
    """One of the faults through the tool itself (every fault against every
    row is ``tests/test_smallthinker_serving.py``'s): experts routed from the
    post-attention state read 5 % off where a sound run reads 1e-7."""
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "tools", "check_smallthinker_variant.py"), CELL,
         "--seed", "5", "--rehearse-on-cpu", "1", "--fault", "router_after_attention"],
        capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    checked = json.loads(proc.stdout.strip().splitlines()[-1].split(": ", 1)[1])
    assert checked["ok"] is False and checked["all_rows"]["p50"] > 100 * checked["tolerance"]
