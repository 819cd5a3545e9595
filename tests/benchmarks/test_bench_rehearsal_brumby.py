"""CPU rehearsal of the Brumby long-generation cell through the benchmark's one
command: the end-to-end line, and the traced line with the metrics that read
the program's counters and its ``setup`` record; and what the device-trace
readers return where there is no trace, no prefill call, or no such scope."""

import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELL = "brumby-longgen-batch"
COUNTERS = {"gen_step_host_self_ms", "gen_lookahead_step_share", "gen_decode_batch_occupancy",
            "gen_state_bytes_per_lane"}


def test_longgen_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"serve_tokens_per_s", "setup_s"}
    assert '"plain_pool_is_own": true' in out


def test_longgen_cell_traced_run_reads_the_counters():
    line, out = rehearse(CELL, trace=1)
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert names == COUNTERS
    every = {m["name"] for m in spec.load_cell(CELL).per_layer}
    for name in every - COUNTERS:        # device-trace metrics find no device plane on the host
        assert f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    # tiny-brumby: 2 layers x 2 kv heads x 768 features x (32 + 1) x float32, one block of the pool
    assert values["gen_state_bytes_per_lane"] == 2 * 2 * 768 * 33 * 4
    assert 0.0 < values["gen_decode_batch_occupancy"] <= 100.0
    assert 0.0 <= values["gen_lookahead_step_share"] <= 100.0


@pytest.mark.parametrize("metric", ["gen_retention_dev_share", "gen_prefill_dev_tokens_per_s",
                                    "gen_retention_prefill_roofline", "gen_retention_decode_roofline",
                                    "gen_pdecode_roofline"])
def test_readers_return_nothing_without_a_trace_and_zero_without_a_prefill(metric, monkeypatch):
    from benchmarks import moe_trace, peaks, serving_trace

    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, True, max_seq_len=64)
    result = {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
              "profile": None, "reduced": None}
    read = spec.load_metric("layer_metrics", metric, cell.root)
    assert read(dict(result)) is None                      # no device trace: left out
    if "prefill" in metric or metric.endswith("_dev_share"):
        decode_only = {"decode": [(0.03, 24)] * 90, "prefill": []}
        monkeypatch.setattr(serving_trace, "classify", lambda r: (decode_only, ""))
        monkeypatch.setattr(moe_trace, "path_seconds", lambda r, path: (0.0, 2.7))
        traced = dict(result)
        assert read(traced) == 0.0
        if "prefill" in metric:
            assert any("no prefill call" in n for n in traced["notes"])
    # a program that does not name the scopes (the parent's) has nothing to read
    from neuronx_distributed_llama3_2_tpu.serving import tracing
    monkeypatch.setattr(tracing, "DETAIL_SCOPES", {"attn": ("qk_norm",)})
    if metric != "gen_prefill_dev_tokens_per_s":
        assert read(dict(result)) is None
