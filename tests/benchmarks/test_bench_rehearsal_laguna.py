"""CPU rehearsal of the Laguna mixed-length cell through the benchmark's one
command — the end-to-end line, and the traced line with the metrics that read
the program's counters and its ``setup`` record — and the cell's readers on
what a traced segment can hold: the byte counts, a segment with no decode call
or no prefill call, a program that lacks what PR 42 added."""

import re

import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import arith_window, peaks, program_trace, spec, window_trace

CELL = "laguna-mixedlen-batch"
COUNTERS = {"mix_expert_useful_flop_share", "mix_expert_load_cv", "mix_step_host_self_ms",
            "mix_lookahead_step_share", "mix_decode_batch_occupancy", "mix_cache_held_share"}


def test_mixedlen_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"serve_tokens_per_s", "setup_s"}
    assert '"clear_margin": 0.001' in out          # the rehearsal's own check sizes


def test_mixedlen_cell_traced_run_reads_the_counters_and_the_setup_record():
    line, out = rehearse(CELL, trace=1)
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert COUNTERS <= names
    every = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert len(every) == 16 and all(n.startswith("mix_") for n in every)
    # device-trace metrics find no device plane on the host and are left out
    for name in every - COUNTERS:
        assert name not in names and f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    # tiny-laguna in the rehearsal: 8 experts, 2 chosen a token, the all-experts path
    assert re.search(r"note: expert pairs asked for .* all \d+ calls", out)
    assert 0.0 < values["mix_expert_useful_flop_share"] <= 25.0
    # a ring of 24 rows a lane (window 8 - 1 + chunk 16, blocks of 4) against contexts of 12-58
    found = re.search(r"note: cache held: (\d+) decode dispatches, mean context (\d+) over ([\d.]+) live lanes, "
                      r"ring 24 rows a lane", out)
    assert found, out[-3000:]
    context = float(found.group(2))
    assert values["mix_cache_held_share"] == pytest.approx(100 * (2 * context + 3 * 24) / (5 * context), abs=2.0)


def test_the_byte_counts_at_the_published_widths():
    assert arith_window.row_bytes(8, 128) == 4096
    assert arith_window.decode_needed_row_bytes(1000, 2, 8, 128) == 1000 * 2 * 4096
    # full layer 29.5 M, window layer 37.9 M parameters of attention (ISSUE 42's sizing)
    assert arith_window.attention_weight_bytes(2048, 48, 8, 128) / 2 == pytest.approx(29.5e6, rel=5e-3)
    assert arith_window.attention_weight_bytes(2048, 64, 8, 128) / 2 == pytest.approx(37.9e6, rel=5e-3)
    weights = arith_window.decode_weight_bytes(
        2048, (48, 64, 64, 64, 48), 8, 128, ("dense",) + ("sparse",) * 4, 8192, 256, 512, 512, 100352)
    # every weight but the embedding table: 3,870 M - 205.5 M parameters, bf16
    assert weights == pytest.approx(2 * (3870e6 - 205.5e6), rel=2e-3)
    # (2 n + 3 x 1,024) / (5 n): 47 % at 8.4k, 60 % at 3k, over 100 % for a lane shorter than its ring
    share = lambda n: arith_window.held_share(n, 1, 2, 4096, 3, 4096, 1024)  # noqa: E731
    assert share(8400) == pytest.approx(0.473, abs=2e-3) and share(3072) == pytest.approx(0.6, abs=1e-3)
    assert share(1024) == pytest.approx(1.0) and share(1536) == pytest.approx(0.8) and share(600) > 1.4


def test_the_shared_readers_book_the_new_scopes_to_attn():
    """``attn/full`` and ``attn/window`` sit between ``attn`` and the block's
    usual children: the shared vocabulary reads what it read."""
    for kind in ("full", "window"):
        for child in ("qkv", "rope", "kv_write", "kv_read", "sdpa", "o_proj"):
            found = program_trace.scopes_of(f"jit(fn)/pdecode/while/body/attn/{kind}/{child}/dot_general:")
            assert found == ("pdecode", "attn", f"attn/{child}"), (kind, child, found)
            assert program_trace.block_of(found) == "attn"
    found = program_trace.scopes_of("jit(fn)/psfx/while/body/attn/window/out_gate/logistic:")
    assert found == ("psfx", "attn") and program_trace.block_of(found) == "attn"
    from neuronx_distributed_llama3_2_tpu.serving import tracing

    assert {"full", "window", "out_gate"} <= set(tracing.DETAIL_SCOPES["attn"])
    assert tracing.SCOPES == program_trace.SCOPES          # the shared vocabulary did not grow
    assert window_trace.names_kinds()


def result_of(records, setup=True):
    """A serving result with hand-made dispatch records and ``setup`` record."""
    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, False, max_seq_len=8704)
    steps = [{"step": i, "events": [("X", "dispatch", float(i), float(i) + 0.01, args)]}
             for i, args in enumerate(records)]
    kinds = {"full": {"layers": 2, "rows_per_lane": None, "row_bytes": 4096},
             "window": {"layers": 3, "rows_per_lane": 1024, "row_bytes": 4096}}
    timeline = {"setup": {"cache_kinds": kinds, "window_ring_rows": 1024} if setup else {}, "routed": []}
    return {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
            "profile": {"engine_steps": steps}, "reduced": None, "timeline": timeline}


def test_the_held_share_follows_the_traced_lanes_contexts():
    read = spec.load_metric("layer_metrics", "mix_cache_held_share", spec.REPO_ROOT)
    records = [{"lanes": 32, "rows": 32 * 3000, "window_rows": 32 * 512},
               {"lanes": 30, "rows": 30 * 3400, "window_rows": 30 * 512}]
    rows, lanes = 32 * 3000 + 30 * 3400, 62
    assert read(result_of(records)) == pytest.approx(100 * (2 * rows + 3 * 1024 * lanes) / (5 * rows))
    assert read(result_of([{"lanes": 4, "rows": 4 * 600, "window_rows": 4 * 512}])) > 100
    # no decode dispatch in the segment: 0 with a note; a program without the record: nothing
    empty = result_of([])
    empty["profile"]["engine_steps"] = [{"step": 0, "events": []}]
    assert read(empty) == 0.0 and any("no decode dispatch" in n for n in empty["notes"])
    assert read(result_of(records, setup=False)) is None
    old = [{"lanes": 32, "rows": 9000}]                  # the parent's record: no window_rows
    assert window_trace.decode_records(result_of(old)) == []


@pytest.mark.parametrize("metric", [
    "mix_full_attn_decode_roofline", "mix_window_attn_decode_roofline", "mix_experts_decode_roofline",
    "mix_pdecode_roofline", "mix_pdecode_dev_p50_ms", "mix_prefill_dev_tokens_per_s",
    "mix_full_attn_dev_share", "mix_window_attn_dev_share"])
def test_a_segment_without_the_program_reads_zero_not_nothing(metric, monkeypatch):
    """A metric the cell lists has to be in every traced line: 0 where the
    program names the scope and the segment ran nothing under it, nothing
    where there is no device trace to read."""
    from benchmarks import moe_trace, serving_trace

    read = spec.load_metric("layer_metrics", metric, spec.REPO_ROOT)
    assert read(result_of([])) is None                     # no device trace: left out
    traced = result_of([{"lanes": 0, "rows": 0, "window_rows": 0}][:0])
    traced["profile"]["engine_steps"] = [{"step": 0, "events": []}]
    monkeypatch.setattr(program_trace, "loaded", lambda r: {"devices": [], "window": (0.0, 3.0)})
    monkeypatch.setattr(program_trace, "program_run_ms", lambda r, scope: None)
    monkeypatch.setattr(moe_trace, "path_seconds", lambda r, path: (0.0, 1.84))
    monkeypatch.setattr(moe_trace, "expert_seconds", lambda r, programs: 0.0)
    monkeypatch.setattr(window_trace.mla_trace, "seconds_in", lambda r, path, programs=None: 0.0)
    only_prefill = {"decode": [], "prefill": [(0.02, 512)] * 40}
    only_decode = {"decode": [(0.021, 32)] * 81, "prefill": []}
    monkeypatch.setattr(serving_trace, "classify",
                        lambda r: (only_decode if "prefill" in metric else only_prefill, ""))
    assert read(traced) == 0.0
    if "prefill" not in metric and "dev_share" not in metric:
        assert any("no pdecode call" in n for n in traced["notes"])
    # a program that does not name the kinds' scopes (the parent's) has nothing to read
    from neuronx_distributed_llama3_2_tpu.serving import tracing
    monkeypatch.setattr(tracing, "DETAIL_SCOPES", {"attn": ("qk_norm",)})
    if metric not in ("mix_pdecode_dev_p50_ms", "mix_prefill_dev_tokens_per_s"):
        assert read(dict(traced)) is None


def test_the_decode_rooflines_count_needed_bytes_over_the_scopes_time(monkeypatch):
    records = [{"lanes": 32, "rows": 32 * 3300, "window_rows": 32 * 512}] * 3
    traced = result_of(records)
    monkeypatch.setattr(program_trace, "loaded", lambda r: {"devices": [], "window": (0.0, 3.0)})
    monkeypatch.setattr(program_trace, "program_run_ms", lambda r, scope: [20.0, 22.0, 21.0])
    seconds = {("attn", "full"): 3 * 9e-3, ("attn", "window"): 3 * 1.5e-3}
    monkeypatch.setattr(window_trace.mla_trace, "seconds_in", lambda r, path, programs=None: seconds[tuple(path)])
    monkeypatch.setattr(window_trace.moe_trace, "expert_seconds", lambda r, programs: 3 * 8.5e-3)
    bw = peaks.PEAKS["TPU v5 lite"].hbm_bytes_per_s
    read = lambda name: spec.load_metric("layer_metrics", name, spec.REPO_ROOT)(traced)  # noqa: E731
    assert read("mix_full_attn_decode_roofline") == pytest.approx(100 * 32 * 3300 * 2 * 4096 / 9e-3 / bw)
    assert read("mix_window_attn_decode_roofline") == pytest.approx(100 * 32 * 512 * 3 * 4096 / 1.5e-3 / bw)
    assert read("mix_experts_decode_roofline") == pytest.approx(100 * 4 * 256 * 3 * 2048 * 512 * 2 / 8.5e-3 / bw)
    whole = read("mix_pdecode_roofline")
    assert 40 < whole < 60 and read("mix_pdecode_dev_p50_ms") == 21.0
    assert all(0 <= read(n) <= 100 for n in (
        "mix_full_attn_decode_roofline", "mix_window_attn_decode_roofline", "mix_experts_decode_roofline"))
