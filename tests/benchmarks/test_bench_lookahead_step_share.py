"""``lookahead_step_share`` and its one-line twins (PR 34): the share of the
traced segment's decode dispatches that the step loop sent ahead of the
device. The reader on hand-made flight-recorder steps, every twin wired to its
cell, and the CPU rehearsal of the cell the mechanism serves (chat: nearly
every decode step runs ahead) and of the one that bypasses it (docs: some lane
is mid-prefill in nearly every step).

The docqa cell has no twin yet: ``test_bench_rehearsal_sarvam.py`` pins that
cell's per-layer list to its 15 entries, and only a ``benchmark`` PR may edit
that file (``PERF.md`` §7): the twin is one entry and a one-line reader away;
``tests/test_async_serving.py`` holds the cell's shape — a queue that is never
empty — to the look-ahead at the engine."""

import pytest

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec
from benchmarks.run import REHEARSAL_PREFIX

TWINS = {
    "mixtral-chat-steady": "lookahead_step_share",
    "olmoe-rag-batch": "rag_lookahead_step_share",
    "mixtral-docs-batch": "docs_lookahead_step_share",
    "mixtral-prefix-pressure": "pressure_lookahead_step_share",
}


def _step(*events):
    return {"t0": 0.0, "t1": 1.0, "events": [("X", name, t0, t0 + 0.1, args) for name, t0, args in events]}


def _result(steps):
    return {"kind": "serving", "profile": {"engine_steps": steps}}


def test_reader_counts_the_decode_dispatches_sent_ahead():
    read = spec.load_metric("layer_metrics", "lookahead_step_share")
    steps = [
        _step(("prefill_chunk", 0.0, {"tokens": 512}), ("dispatch", 0.2, {"mode": "sync", "lanes": 3})),
        _step(("dispatch", 1.0, {"mode": "async", "lanes": 3})),
        _step(("dispatch", 2.0, {"mode": "async", "lanes": 3}), ("readback", 2.2, {})),
        _step(("dispatch", 3.0, {"mode": "async", "lanes": 2})),
    ]
    assert read(_result(steps)) == pytest.approx(75.0)


def test_reader_gives_a_number_for_a_program_that_never_runs_ahead():
    """The parent of PR 34 books every decode dispatch ``sync``: 0.0, a number."""
    read = spec.load_metric("layer_metrics", "lookahead_step_share")
    steps = [_step(("dispatch", float(i), {"mode": "sync", "lanes": 1})) for i in range(5)]
    assert read(_result(steps)) == 0.0
    # records older than the field read the same
    assert read(_result([_step(("dispatch", 0.0, {"lanes": 1}))])) == 0.0


def test_reader_reads_zero_where_the_segment_held_no_decode_and_nothing_without_a_recorder():
    read = spec.load_metric("layer_metrics", "lookahead_step_share")
    result = _result([_step(("prefill", 0.0, {"tokens": 64}))])
    assert read(result) == 0.0 and "no decode dispatch" in result["notes"][0]
    assert read({"kind": "serving", "profile": None}) is None
    assert read({"kind": "serving", "profile": {"engine_steps": []}}) is None
    assert read({"kind": "training"}) is None


@pytest.mark.parametrize("cell", sorted(TWINS))
def test_each_serving_cell_reports_its_twin_and_no_other(cell):
    names = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert names & set(TWINS.values()) == {TWINS[cell]}
    row = next(m for m in spec.load_cell(cell).per_layer if m["name"] == TWINS[cell])
    assert (row["unit"], row["better"], row["source"], row["layer"]) == ("%", "higher", "program_counter", "scheduler")
    assert row["moves"] in {m["name"] for m in spec.load_cell(cell).end_to_end}
    assert spec.load_metric("layer_metrics", TWINS[cell]) is not None


@pytest.mark.parametrize("cell", ["pythia-train-tp2pp2", "sarvam-docqa-batch"])
def test_the_training_cell_and_the_pinned_docqa_cell_report_none_of_them(cell):
    names = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert not names & set(TWINS.values())


@pytest.mark.parametrize("cell", ["mixtral-chat-steady", "mixtral-docs-batch"])
def test_traced_rehearsal_reports_the_share(cell):
    line, _ = rehearse(cell, trace=1)
    names = check_line(line, spec.load_cell(cell), trace=1)
    assert TWINS[cell] in names
    share = line["metrics"][REHEARSAL_PREFIX + TWINS[cell]]["value"]
    assert 0.0 <= share <= 100.0
    if cell == "mixtral-chat-steady":
        # an open loop well under its knee: most decode steps carry no
        # scheduler event
        assert share > 50.0

