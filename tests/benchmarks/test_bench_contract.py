"""``BENCHMARK.json`` against the driver's contract, the result line, the peaks
table and the FLOP arithmetic."""

import json
import os
import re

import pytest

from benchmarks import arith, peaks, run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(bench["command"]) <= 32 and bench["command"][1].startswith("benchmarks/")
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    rs, cells = bench["run_seconds"], 24
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200
    assert 2 <= len(bench["workloads"]) <= 24 and 1 <= len(bench["configs"]) <= 24


def test_entries_have_exactly_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and os.path.exists(os.path.join(spec.REPO_ROOT, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or "hidden_size" in k or "intermediate" in k
                       for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128


def test_names_units_and_lines(bench):
    rows = bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]
    for row in rows:
        assert NAME.match(row["name"]), row["name"]
        for key in ("why", "layer", "source"):
            if key in row:
                assert 1 <= len(row[key]) <= 200 and "\n" not in row[key] and "\t" not in row[key], row
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for group in ("configs", "workloads"):
        names = [r["name"] for r in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert all(NAME.match(w["config"]) and NAME.match(w["traffic"]) for w in bench["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for root, _, files in os.walk(spec.HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)


def test_cells_configs_and_metrics_hang_together(bench):
    cells = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == configs      # every config has a cell
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
    for name in cells:
        cell = spec.load_cell(name)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2, name
        assert cell.per_layer, name
        assert all(m["moves"] in reported for m in cell.per_layer)
        assert cell.traffic["kind"] in ("open_poisson", "closed_loop", "train_job")
        assert cell.config["chips"] == cell.chips
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(spec.REPO_ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(f"| {layer} |" in perf for layer in layers), layers


def test_result_line_has_the_drivers_keys():
    line = json.loads(run.result_line(
        True, 400, 0, {"ttft_p50_ms": (212.4071, "ms"), "setup_s": (95.3127, "s")},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 13958643712},
    ))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["metrics"]["ttft_p50_ms"] == {"value": 212.4071, "unit": "ms"}
    traced = json.loads(run.result_line(True, 1, 0, {}, {}, {"device_ops": [], "idle_gaps": []}))
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_an_unknown_chip_is_an_error_not_a_default():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="not in benchmarks/peaks.py"):
        peaks.peaks_for("TPU v9 imaginary")


def test_flop_arithmetic():
    shapes = {"['embed']['embedding']": (1000, 64), "['lm_head']['kernel']": (64, 1000),
              "['layers']['mlp']['up']['kernel']": (2, 64, 256), "['layers']['mlp']['up']['bias']": (2, 256),
              "['final_norm']['scale']": (64,)}
    n = arith.matmul_params(shapes)
    assert n == 64 * 1000 + 2 * 64 * 256     # no embedding, no norm, no (stacked, 2-d) bias
    per_token = arith.train_flops_per_token(n, num_layers=2, num_heads=4, head_dim=16, seq_len=128)
    assert per_token == 6 * n + 3 * (2 * 128 * 4 * 16) * 2      # causal: half of 4*S*h forward
    assert arith.mfu(1000.0, 197e9, 4, 197e12) == pytest.approx(0.25)
    flops, nbytes = arith.flash_call_cost("flash_fwd", 2, 16, 16, 2048, 128)
    assert flops == 2 * (2 * 2 * 16 * 2048 * 2048 * 128) / 2
    assert nbytes == 4 * 2 * 16 * 2048 * 128 * 2
    assert arith.flash_call_cost("flash_bwd_dkv", 2, 16, 16, 2048, 128)[0] == 2 * flops
    assert arith.roofline_seconds(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    assert arith.roofline_seconds(1.0, 819e9, 197e12, 819e9) == (1.0, "memory")


def test_started_as_a_script_the_command_shadows_no_module_with_its_own_files():
    """``python3 benchmarks/run.py`` puts ``benchmarks/`` first on the path,
    where ``profile.py`` would be the standard library's ``profile``."""
    import subprocess
    import sys

    code = (
        "import runpy, sys\n"
        f"sys.path.insert(0, {spec.HERE!r})\n"            # as the interpreter does for a script
        f"runpy.run_path({os.path.join(spec.HERE, 'run.py')!r}, run_name='bench_run')\n"
        "import cProfile, profile\n"                      # cProfile needs the real `profile`
        f"assert {spec.HERE!r} not in sys.path and {spec.REPO_ROOT!r} == sys.path[0]\n"
        f"assert not profile.__file__.startswith({spec.HERE!r})\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, cwd=spec.REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
