"""CPU rehearsal of the sarvam document-QA cell through the benchmark's one
command: the end-to-end line, and the traced line with the metrics that read
the program's counters and its ``setup`` record."""

import re

import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELL = "sarvam-docqa-batch"


def test_docqa_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"serve_tokens_per_s", "setup_s"}
    assert '"clear_margin": 0.001' in out          # the rehearsal's own check sizes


def test_docqa_cell_traced_run_reads_the_counters():
    line, out = rehearse(CELL, trace=1)
    names = check_line(line, spec.load_cell(CELL), trace=1)
    counters = {"qa_step_host_self_ms", "qa_prefix_hit_rate", "qa_expert_local_pair_share",
                "qa_expert_useful_flop_share", "qa_cache_row_bytes"}
    assert counters <= names
    # device-trace metrics find no device plane on the host and are left out
    every = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert len(every) == 15 and all(n.startswith("qa_") for n in every)
    for name in every - counters:
        assert name not in names and f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    # tiny-sarvam in the rehearsal: 2 of 8 experts held, 2 chosen a token, the
    # all-held path computes both for every row
    assert re.search(r"note: expert dispatch paths in the traced segment: \['all'\]", out)
    assert 5.0 < values["qa_expert_local_pair_share"] < 60.0
    assert 0.0 < values["qa_expert_useful_flop_share"] <= 100.0 * 2 / 8 * 2.5
    assert values["qa_prefix_hit_rate"] > 50.0
    assert values["qa_cache_row_bytes"] == 128 * 4        # 40 float32 values in one lane of 128


@pytest.mark.parametrize("metric", ["qa_latent_up_dev_share", "qa_shared_expert_dev_share",
                                    "qa_prefill_dev_tokens_per_s", "qa_mla_prefill_roofline"])
def test_a_decode_only_traced_segment_reads_zero_not_nothing(metric, monkeypatch):
    """The cell's requests run in lockstep waves, so a 3 s traced segment is
    often a wave's decode phase alone. A metric the cell lists has to be in the
    line all the same: 0 where the program names the scope and ran nothing
    under it, nothing where there is no device trace to read."""
    from benchmarks import moe_trace, peaks, serving_trace

    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, True, max_seq_len=64)
    result = {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
              "profile": None, "reduced": None}
    read = spec.load_metric("layer_metrics", metric, cell.root)
    assert read(dict(result)) is None                      # no device trace: left out
    decode_only = {"decode": [(0.021, 64)] * 81, "prefill": []}
    monkeypatch.setattr(serving_trace, "classify", lambda r: (decode_only, ""))
    monkeypatch.setattr(moe_trace, "path_seconds", lambda r, path: (0.0, 1.84))
    traced = dict(result)
    assert read(traced) == 0.0
    if "prefill" in metric:
        assert any("no prefill call" in n for n in traced["notes"])
    # a program that does not name the scope (the parent's) has nothing to read
    from neuronx_distributed_llama3_2_tpu.serving import tracing
    monkeypatch.setattr(tracing, "DETAIL_SCOPES", {"attn": ("qk_norm",)})
    if metric.endswith("_dev_share"):
        assert read(dict(result)) is None
