"""CPU rehearsal of the closed-loop serving cell, and of a fourth cell added
with two new files and one ``workloads`` entry — no edit to any file that is
there."""

import json
import os
import shutil

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec


def test_docs_cell_end_to_end_metrics():
    line, _ = rehearse("mixtral-docs-batch", trace=0)
    names = check_line(line, spec.load_cell("mixtral-docs-batch"), trace=0)
    assert names == {"serve_tokens_per_s", "setup_s"}


def test_a_fourth_cell_is_two_new_files_and_one_entry(tmp_path):
    root = str(tmp_path)
    shutil.copytree(spec.HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.relpath(os.path.join(d, f), root): open(os.path.join(d, f), "rb").read()
        for d, _, fs in os.walk(root) for f in fs
    }
    # new file 1: a traffic mix (data only) — bursty short chat
    with open(os.path.join(spec.HERE, "traffic", "chat-steady.json")) as f:
        mix = json.load(f)
    mix["doc"] = "short prompts arriving in bursts"
    mix["rehearsal"]["arrivals"] = {"rate_rps": 8.0, "cv": 3.0}
    mix["rehearsal"]["prompt_tokens"] = {"dist": "log_uniform", "low": 4, "high": 16}
    mix["rehearsal"]["sharing"] = {"share": 0.0}
    with open(os.path.join(root, "benchmarks", "traffic", "chat-bursty.json"), "w") as f:
        json.dump(mix, f)
    # new file 2: a per-layer metric with a reader of its own
    with open(os.path.join(root, "benchmarks", "layer_metrics", "ttft_max_ms.py"), "w") as f:
        f.write("from benchmarks import serving\n\n\ndef read(r):\n"
                "    return max(serving.ttft_ms(r['in_window']), default=None)\n")
    # one workloads entry (and the metric's own entry; cells listed per metric)
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "mixtral-chat-bursty", "config": "mixtral-8x7b-1chip",
        "traffic": "chat-bursty", "chips": 1, "why": "bursts of short prompts",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mixtral-chat-steady" in m.get("workloads", []):
            m["workloads"].append("mixtral-chat-bursty")
    bench["per_layer"].append({
        "name": "ttft_max_ms", "unit": "ms", "better": "lower", "source": "host_clock",
        "layer": "front door", "moves": "ttft_p50_ms", "workloads": ["mixtral-chat-bursty"],
    })
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)

    line, _ = rehearse("mixtral-chat-bursty", trace=1, extra=("--benchmark-json", path))
    cell = spec.load_cell("mixtral-chat-bursty", path)
    names = check_line(line, cell, trace=1)
    assert "ttft_max_ms" in names and "gen_late_p90_ms" in names
    after = {
        rel: open(os.path.join(root, rel), "rb").read() for rel in before
    }
    assert after == before            # nothing that was there was edited
