"""``BENCHMARK.json``'s lists grow at the end: the entries each earlier PR
added keep their order, a later PR's come after them, and every per-layer
entry has a reader and a cell that reports it. Nothing here pins the newest
block to the end or a list to its present length: the next PR appends."""

import json
import os

import pytest

from benchmarks import spec

# (list, first and last entry a PR added): each block follows the one before
BLOCKS = [
    ("per_layer", "gen_late_p90_ms", "train_device_idle_share"),          # PR 22
    ("per_layer", "door_pre_submit_p50_ms", "pipeline_bubble_share"),     # PR 23
    ("per_layer", "rag_moe_dev_share", "pressure_door_first_write_p50_ms"),   # PR 26
    ("workloads", "mixtral-chat-steady", "pythia-train-tp2pp2"),
    ("workloads", "olmoe-rag-batch", "mixtral-prefix-pressure"),
    ("configs", "mixtral-8x7b-1chip", "pythia-6.9b-4chip"),
    ("configs", "olmoe-1b-7b-1chip", "olmoe-1b-7b-1chip"),
]
# the cells each end-to-end list started with (PR 22), then PR 26's
E2E_STARTS_WITH = {
    "ttft_p50_ms": ["mixtral-chat-steady", "mixtral-prefix-pressure"],
    "tpot_p50_ms": ["mixtral-chat-steady", "mixtral-prefix-pressure"],
    "serve_tokens_per_s": ["mixtral-docs-batch", "olmoe-rag-batch"],
    "train_tokens_per_s_per_chip": ["pythia-train-tp2pp2"],
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_each_prs_entries_follow_the_ones_before(bench):
    at = {}
    for group, first, last in BLOCKS:
        names = [e["name"] for e in bench[group]]
        assert at.get(group, -1) < names.index(first) <= names.index(last), (group, first, last)
        at[group] = names.index(last)


def test_every_per_layer_entry_has_a_reader_and_a_cell_that_reports_it(bench):
    reported = set()
    for cell in (w["name"] for w in bench["workloads"]):
        loaded = spec.load_cell(cell)
        for m in loaded.per_layer:
            assert callable(spec.load_metric("layer_metrics", m["name"], loaded.root)), m["name"]
            reported.add(m["name"])
    assert reported == {m["name"] for m in bench["per_layer"]}


@pytest.mark.parametrize("metric", sorted(E2E_STARTS_WITH))
def test_end_to_end_lists_grow_at_the_end(bench, metric):
    cells = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}[metric]
    assert cells[:len(E2E_STARTS_WITH[metric])] == E2E_STARTS_WITH[metric]
