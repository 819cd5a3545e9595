"""CPU rehearsal of the Xing4.0 long-document cell through the benchmark's one
command — the end-to-end line, and the traced line with the metrics that read
the program's counters and its ``setup`` record — and the cell's readers on
what a traced segment can hold: the byte counts of a decode step, a segment
with no call of one kind, a program that lacks what PR 44 added; and the
long-prompt check (``tools/check_long_rows.py``) at the tiny size."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from bench_rehearsal_util import check_line, rehearse
from benchmarks import peaks, program_trace, residual_trace, spec

CELL = "xing-longdoc-batch"
# what docqa's cell reads too is read by its readers, which list both cells
SHARED = {"qa_mla_dev_share", "qa_latent_up_dev_share", "qa_moe_dev_share", "qa_shared_expert_dev_share",
          "qa_pdecode_dev_p50_ms", "qa_prefill_dev_tokens_per_s", "qa_device_idle_share",
          "qa_step_host_self_ms", "qa_prefix_hit_rate", "qa_cache_row_bytes", "qa_mla_decode_roofline",
          "qa_mla_prefill_roofline", "qa_experts_decode_roofline"}
OWN = {"ldoc_pdecode_roofline", "ldoc_mhc_dev_share", "ldoc_mhc_ops_per_step", "ldoc_residual_row_bytes",
       "ldoc_lookahead_step_share", "ldoc_decode_batch_occupancy"}
COUNTERS = {"qa_step_host_self_ms", "qa_prefix_hit_rate", "qa_cache_row_bytes", "ldoc_residual_row_bytes",
            "ldoc_lookahead_step_share", "ldoc_decode_batch_occupancy"}
EVERY = SHARED | OWN


def test_longdoc_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"serve_tokens_per_s", "setup_s"}
    assert '"clear_margin": 0.001' in out          # the rehearsal's own check sizes


def test_longdoc_cell_traced_run_reads_the_counters_and_the_setup_record():
    line, out = rehearse(CELL, trace=1)
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert COUNTERS <= names
    assert {m["name"] for m in spec.load_cell(CELL).per_layer} == EVERY
    # device-trace metrics find no device plane on the host and are left out
    for name in EVERY - COUNTERS:
        assert name not in names and f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    assert values["ldoc_residual_row_bytes"] == 4 * 64 * 4      # tiny-xing: 4 streams of 64 float32
    assert values["qa_cache_row_bytes"] == 128 * 4              # 40 float32 values in one lane of 128
    assert values["qa_prefix_hit_rate"] > 50.0
    assert 0.0 < values["ldoc_decode_batch_occupancy"] <= 100.0
    assert 0.0 <= values["ldoc_lookahead_step_share"] <= 100.0


def test_the_shared_readers_list_both_latent_cells_and_nothing_is_read_twice():
    """The cell adds no second name for a quantity docqa's cell already
    reads: thirteen ``qa_`` readers list both cells, six readers are its own."""
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        rows = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in SHARED:
        assert rows[name]["workloads"] == ["sarvam-docqa-batch", CELL], name
    for name in OWN:
        assert rows[name]["workloads"] == [CELL] and rows[name]["moves"] == "serve_tokens_per_s", name
    assert sorted(n for n in rows if n.startswith("ldoc_")) == sorted(OWN)
    assert len(rows) <= 128 - 7           # room left for later cells' own metrics


def test_the_cell_as_the_issue_gives_it():
    cell = spec.load_cell(CELL)
    t, e = cell.traffic, cell.traffic["engine"]
    assert (cell.chips, cell.config_name, t["kind"], t["clients"], e["lanes"]) == (
        1, "xing4-29b-a4b-1chip", "closed_loop", 40, 32)
    assert t["sharing"] == {"share": 1.0, "prefixes": 16, "prefix_tokens": 14336, "min_own_tokens": 64}
    assert t["prompt_tokens"] == {"dist": "log_uniform", "low": 14400, "high": 16128}
    assert (t["output_tokens"], e["block_size"], e["max_seq_len"]) == (256, 16, 16384)
    assert e["prefill_chunk_tokens"] == 512 and e["kv_buckets"][-1] == 16384 and "schedule_seed" in t
    # the pool is the issue's: the documents' blocks, every lane's own, 2,048 spare
    assert e["pool_blocks"] == 16 * 896 + 32 * 128 + 2048 == 20480
    # moved, each with its reason in the file's `doc`: the lead-in (lengthened, never shortened),
    # the traced segment (longer than the longest stretch of decode alone), the lower kv rung (dropped)
    assert t["lead_s"] >= 45.0 and t["trace_s"] > 6.5 and e["kv_buckets"] == [16384]
    for word in ("lead_s 150", "trace_s 8", "kv_buckets is [16384] alone", "NOT the 6,144", "NOT met"):
        assert word in t["doc"], word
    # the check's prompt: six whole chunks (pctx, then five psfx over the 16,384 rung) — not the
    # 6,144 the issue asked for: its float32 logits are 131,072 wide and check.py holds them twice
    # on the device; tools/check_long_rows.py compares a 15k-row prompt on a sample of rows, and
    # the rehearsal's check crosses YaRN's original range (32 there)
    assert t["check"]["prompt_tokens"] == 6 * 512
    assert cell.for_rehearsal().traffic["check"]["prompt_tokens"] > 32
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    assert {m["layer"] for m in cell.per_layer} == {"decode programs", "scheduler", "device"}


def test_the_long_prompt_check_keeps_rows_on_both_sides_of_the_original_range():
    sys.path.insert(0, os.path.join(spec.HERE, "tools"))
    try:
        import check_long_rows
    finally:
        sys.path.pop(0)
    keep = check_long_rows.rows_kept(15360, 16, 32, 4096)
    assert keep[0] == 0 and keep[-1] == 15375 and len(keep) == len(set(keep.tolist()))
    assert set(range(15359, 15376)) <= set(keep.tolist())             # the rows the engine's tokens are read from
    for edge in (4096, 8192, 12288):
        assert set(range(edge - 8, edge + 8)) <= set(keep.tolist())
    assert len(keep) * 131072 * 4 < 0.3e9                             # the float32 logits kept: under 0.3 GB
    assert np.all(np.diff(keep) > 0)


def test_the_long_prompt_check_runs_the_engine_and_its_programs_past_the_original_range():
    """The tool's rehearsal: a prompt that fills the tiny engine's context
    (56 of 64 rows, the rotary tables' original range 32), tokens through
    ``submit`` / ``step``, rows through the paged programs' calls, every band
    within the rehearsal's tolerance of the float32 reference."""
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "tools", "check_long_rows.py"), CELL, "--seed", "2147483999",
         "--prompt-tokens", "56", "--every", "4", "--rehearse-on-cpu", "1"],
        capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("seed 2147483999"))
    got = json.loads(line[line.index("}: {") + 3:])           # "seed n set-up {...}: {...}"
    assert got["ok"] is True and got["prompt_tokens"] == 56 and got["original_max_position_embeddings"] == 32
    assert set(got["bands"]) == {"prompt rows 0-31", "prompt rows 32-55", "decode rows"}
    assert all(b["rows"] > 0 and b["p50"] < 1e-4 for b in got["bands"].values()), got["bands"]
    assert got["engine_tokens"]["near_reference_max"] == 1.0


def test_the_shared_readers_see_the_new_scopes_where_they_belong():
    """``mhc`` sits outside every block — the shared vocabulary books it to
    the program's root and ``attn``'s seconds stay what docqa's metrics read —
    and ``attn/q_latent`` is booked to ``attn``."""
    for child in ("coeff", "sinkhorn", "mix"):
        found = program_trace.scopes_of(f"jit(fn)/pdecode/while/body/mhc/{child}/mul:")
        assert found == ("pdecode",) and program_trace.block_of(found) is None, (child, found)
    found = program_trace.scopes_of("jit(fn)/psfx/while/body/attn/q_latent/dot_general:")
    assert found == ("psfx", "attn") and program_trace.block_of(found) == "attn"
    from neuronx_distributed_llama3_2_tpu.serving import tracing

    assert tracing.DETAIL_SCOPES["mhc"] == ("coeff", "sinkhorn", "mix")
    assert "q_latent" in tracing.DETAIL_SCOPES["attn"]
    assert tracing.SCOPES == program_trace.SCOPES          # the shared vocabulary did not grow
    assert residual_trace.names_residual()


def result_of(records, setup=True):
    """A serving result with hand-made dispatch records and ``setup`` record."""
    cell = spec.load_cell(CELL)
    cfg = spec.load_family(cell.config["family"]).model_config(cell.config, False, max_seq_len=16384)
    steps = [{"step": i, "events": [("X", "dispatch", float(i), float(i) + 0.01, args)]}
             for i, args in enumerate(records)]
    timeline = {"setup": {"residual_row_bytes": 28672, "cache_row_bytes": 1280} if setup else {}, "routed": []}
    return {"kind": "serving", "cell": cell, "model_cfg": cfg, "peaks": peaks.PEAKS["TPU v5 lite"],
            "profile": {"engine_steps": steps}, "reduced": None, "timeline": timeline}


def read(name, result):
    return spec.load_metric("layer_metrics", name, spec.REPO_ROOT)(result)


def test_the_counter_reads_the_setup_record_and_nothing_without_it():
    assert read("ldoc_residual_row_bytes", result_of([])) == 28672.0
    assert read("ldoc_residual_row_bytes", result_of([], setup=False)) is None


def test_the_decode_rooflines_count_needed_bytes_over_device_time(monkeypatch):
    from benchmarks import mla_trace, moe_trace

    records = [{"lanes": 32, "rows": 32 * 15400, "kind": "decode"}] * 3
    traced = result_of(records)
    monkeypatch.setattr(mla_trace, "decode_rows", lambda r: [32 * 15400] * 3)
    monkeypatch.setattr(program_trace, "loaded", lambda r: {"devices": [], "window": (0.0, 3.0)})
    monkeypatch.setattr(program_trace, "program_run_ms", lambda r, scope: [36.0, 38.0, 37.0])
    monkeypatch.setattr(moe_trace, "program_calls", lambda r, kinds: 3)
    monkeypatch.setattr(mla_trace, "seconds_in", lambda r, path, programs=None: 3 * 24e-3)
    monkeypatch.setattr(moe_trace, "expert_seconds", lambda r, programs: 3 * 8e-3)
    bw = peaks.PEAKS["TPU v5 lite"].hbm_bytes_per_s
    rows_bytes = 32 * 15400 * 5 * 1152
    assert read("qa_mla_decode_roofline", traced) == pytest.approx(100 * rows_bytes / 24e-3 / bw)
    assert read("qa_experts_decode_roofline", traced) == pytest.approx(
        100 * 4 * 64 * 3 * 3584 * 1024 * 2 / 8e-3 / bw)
    whole = read("ldoc_pdecode_roofline", traced)
    assert whole == pytest.approx(100 * (7.16e9 + rows_bytes) / 37e-3 / bw, rel=5e-3)
    assert read("qa_pdecode_dev_p50_ms", traced) == 37.0
    assert all(0 < read(n, traced) <= 100 for n in (
        "qa_mla_decode_roofline", "qa_experts_decode_roofline", "ldoc_pdecode_roofline"))
    assert any("a decode step needs 7.16 GB of weights + 2.83" in n for n in traced["notes"]), traced["notes"]


@pytest.mark.parametrize("metric,without", [
    ("qa_mla_decode_roofline", None), ("qa_experts_decode_roofline", None), ("qa_pdecode_dev_p50_ms", None),
    ("ldoc_pdecode_roofline", None), ("ldoc_mhc_ops_per_step", None),
    ("qa_mla_prefill_roofline", 0.0), ("qa_prefill_dev_tokens_per_s", 0.0), ("ldoc_mhc_dev_share", 0.0)])
def test_a_segment_without_calls_of_a_kind(metric, without, monkeypatch):
    """What a reader gives for a traced segment that ran no call of its kind:
    nothing where it reads ``pdecode`` (the cell's ``trace_s`` outlasts every
    stretch of prefill alone, so that no traced line lacks it), 0 where it
    reads prefill or a share of the busy time (the ``qa_`` readers' rule); and
    nothing at all from a program that lacks the residual (the parent's)."""
    from benchmarks import moe_trace, serving_trace

    assert read(metric, result_of([])) is None                     # no device trace: left out
    traced = result_of([])
    traced["profile"]["engine_steps"] = [{"step": 0, "events": []}]
    monkeypatch.setattr(program_trace, "loaded", lambda r: {"devices": [], "window": (0.0, 8.0)})
    monkeypatch.setattr(program_trace, "program_run_ms", lambda r, scope: None)
    monkeypatch.setattr(moe_trace, "path_seconds", lambda r, path: (0.0, 1.84))
    monkeypatch.setattr(moe_trace, "expert_seconds", lambda r, programs: 0.0)
    monkeypatch.setattr(residual_trace, "under", lambda r, path, programs=None: (0.0, 0))
    only_prefill = {"decode": [], "prefill": [(0.02, 512)] * 40}
    only_decode = {"decode": [(0.037, 32)] * 81, "prefill": []}
    monkeypatch.setattr(serving_trace, "classify",
                        lambda r: (only_decode if "prefill" in metric else only_prefill, ""))
    assert read(metric, traced) == without
    if metric.startswith("ldoc_mhc"):
        from neuronx_distributed_llama3_2_tpu.serving import tracing
        monkeypatch.setattr(tracing, "DETAIL_SCOPES", {"attn": ("qk_norm",)})
        assert read(metric, dict(traced)) is None
