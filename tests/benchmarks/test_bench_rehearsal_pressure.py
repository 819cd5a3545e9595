"""CPU rehearsal of the prefix-pressure cell (a traffic file and entries, no
code): the shared prefixes alone fill the rehearsal's pool, so blocks are
evicted inside the window and the hit rate stays under what sharing offers."""

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELL = "mixtral-prefix-pressure"


def test_pressure_cell_end_to_end_metrics():
    line, _ = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}


def test_pressure_cell_traced_run_reads_evictions_and_preemptions():
    line, out = rehearse(CELL, trace=1)
    cell = spec.load_cell(CELL)
    names = check_line(line, cell, trace=1)
    assert {"pressure_prefix_hit_rate", "pressure_queue_wait_p50_ms",
            "pressure_admit_to_first_token_p50_ms", "pressure_evicted_blocks_per_request",
            "pressure_preemptions", "pressure_step_host_self_ms", "pressure_door_pre_submit_p50_ms",
            "pressure_door_first_write_p50_ms"} <= names
    assert "pressure_moe_dev_share" not in names and "pressure_kv_dev_share" not in names
    assert "pressure_decode_step_dev_ms" not in names and "pressure_device_idle_share" not in names
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    assert values["pressure_evicted_blocks_per_request"] > 0
    sharing = cell.traffic["sharing"]
    assert 0.0 < values["pressure_prefix_hit_rate"] < 100.0 * sharing["share"]
