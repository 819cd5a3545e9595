"""The ``start-up`` layer's readers (``benchmarks/setup_trace.py``): their
arithmetic on a hand-written set-up record, what they return over a program
that has no recorder, and one traced CPU rehearsal of a serving and of the
training cell."""

import re

import pytest

from bench_rehearsal_util import check_line, rehearse
from benchmarks import setup_trace, spec
from benchmarks.run import REHEARSAL_PREFIX

SPAN_METRICS = (
    "setup_before_runtime_s", "setup_runtime_start_s", "setup_engine_build_s",
    "setup_cost_profiles_s", "setup_prewarm_s", "setup_outside_spans_s",
)
COUNTER_METRICS = ("setup_trace_lower_s", "setup_compile_s", "setup_cache_misses")


def hand_written():
    """A start of 30 s (origin 100): runtime 103–108, an inference engine, two
    paged engines of which the second ends after set-up's end, and events of
    which one lies under ``setup.facts`` and one after the end."""
    spans = [
        ["setup.runtime", 103.0, 108.0, None, {}],                               # 0
        ["setup.inference_engine", 110.0, 111.0, None, {}],                      # 1
        ["setup.placement", 110.25, 110.75, 1, {}],                              # 2
        ["setup.paged_engine", 112.0, 122.0, None, {}],                          # 3
        ["setup.prewarm", 113.0, 118.0, 3, {}],                                  # 4
        ["setup.program", 113.0, 116.0, 4, {"key": "('pctx', 64, SamplingConfig(greedy=True), False)", "kind": "pctx"}],
        ["setup.program", 116.0, 118.0, 4, {"key": "('pdecode', 64)", "kind": "pdecode"}],    # 6
        ["setup.mark_steady", 118.0, 118.5, 3, {}],                              # 7
        ["setup.cost_profiles", 118.5, 120.5, 3, {}],                            # 8
        ["setup.facts", 120.5, 122.0, 3, {}],                                    # 9
        ["setup.paged_engine", 126.0, 131.0, None, {}],                          # 10: ends after 130
        ["setup.prewarm", 126.5, 129.0, 10, {}],                                 # 11: its parent is cut
        ["setup.cost_profiles", 140.0, 141.0, None, {}],                         # 12: after the end
    ]
    events = [
        [109.0, "trace", 0.5, "init", None], [109.5, "lower", 0.25, "jit(init)", None],
        [109.75, "compile", 0.125, "jit(init)", None], [109.6, "cache_request", 0.0, None, None],
        [109.7, "cache_hit", 0.0, None, None],
        # an inner jit's trace reported inside its caller's: [113, 115] holds [113.5, 114.5]
        [114.5, "trace", 1.0, "inner", 5], [115.0, "trace", 2.0, "fn", 5],
        [115.5, "lower", 0.5, "jit(fn)", 5], [116.0, "compile", 0.5, "jit(fn)", 5],
        [115.6, "cache_request", 0.0, None, 5],                                   # a miss
        [117.0, "lower", 0.25, "jit(fn)", 6], [117.5, "compile", 0.25, "jit(fn)", 6],
        [117.1, "cache_request", 0.0, None, 6], [117.2, "cache_hit", 0.0, None, 6],
        [119.0, "lower", 1.0, "jit(fn)", 8],                                      # the harvest's second lowering
        [121.0, "lower", 1.0, "jit(fn)", 9], [121.5, "compile", 0.5, "jit(fn)", 9],   # under facts: left out
        [121.1, "cache_request", 0.0, None, 9],
        [135.0, "compile", 3.0, "jit(fn)", None], [135.0, "cache_request", 0.0, None, None],    # after the end
    ]
    return {"setup_s": 30.0, "setup_record": {"origin": 100.0, "spans": spans, "events": events}}


WANT = {
    "setup_before_runtime_s": 3.0,
    "setup_runtime_start_s": 5.0,
    # 1 + 10 (the engines that ended by 130) less prewarm 5, cost profiles 2, facts 1.5
    "setup_engine_build_s": 2.5,
    "setup_cost_profiles_s": 2.0,
    "setup_prewarm_s": 5.0 + 2.5,        # spans of one name are summed; the second ended by 130
    # 130 − 108 less the roots that ended by then (1 + 10): the third engine is still open
    "setup_outside_spans_s": 11.0,
    # traces: [108.5, 109] and the union [113, 115] = 2.5; lowerings 0.25 + 0.5 + 0.25 + 1.0
    "setup_trace_lower_s": 4.5,
    "setup_compile_s": 0.125 + 0.5 + 0.25,
    "setup_cache_misses": 1.0,           # three requests, two hits; facts' and the late one left out
}


@pytest.mark.parametrize("metric", SPAN_METRICS + COUNTER_METRICS)
def test_reader_arithmetic_on_a_hand_written_record(metric):
    result = hand_written()
    value = spec.load_metric("layer_metrics", metric)(result)
    assert value == pytest.approx(WANT[metric]), metric
    assert isinstance(value, float)
    if metric == "setup_prewarm_s":
        note, = result["notes"]
        assert note.startswith("setup.prewarm, costliest programs: ('pctx', 64, cfg, False) 3.00 s "
                               "(trace + lower 2.50, compile 0.50, miss); ('pdecode', 64) 2.00 s "
                               "(trace + lower 0.25, compile 0.25, hit)")
    if metric == "setup_outside_spans_s":
        assert result["notes"] == ["setup.facts 1.500 s: traced engines only, in no metric"]


def test_the_span_metrics_and_facts_sum_to_the_cut():
    """With no engine open at set-up's end the six span metrics and
    ``setup.facts`` are the whole of ``setup_s``."""
    result = hand_written()
    del result["setup_record"]["spans"][10:12]
    total = sum(spec.load_metric("layer_metrics", m)(result) for m in SPAN_METRICS)
    assert total + setup_trace.span_seconds(result, "setup.facts") == pytest.approx(result["setup_s"])


@pytest.mark.parametrize("metric", SPAN_METRICS + COUNTER_METRICS)
def test_a_program_without_the_recorder_gives_nothing_to_read(metric, monkeypatch):
    monkeypatch.setattr(setup_trace, "setup_record", None)           # the parent has no such module
    assert spec.load_metric("layer_metrics", metric)({"setup_s": 30.0, "kind": "serving"}) is None


@pytest.mark.parametrize("metric", ("setup_engine_build_s", "setup_prewarm_s", "setup_outside_spans_s",
                                    "setup_before_runtime_s"))
def test_a_span_that_was_never_opened_gives_nothing_to_read(metric):
    # a training process: compile-path events and nothing else
    result = {"setup_s": 30.0, "setup_record": {"origin": 100.0, "spans": [], "events": [
        [105.0, "trace", 1.0, "train_step", None], [106.0, "compile", 0.5, "jit(train_step)", None]]}}
    assert spec.load_metric("layer_metrics", metric)(result) is None
    assert spec.load_metric("layer_metrics", "setup_compile_s")(result) == 0.5


@pytest.mark.parametrize("cell,devices", [("mixtral-docs-batch", 1), ("pythia-train-tp2pp2", 4)])
def test_traced_rehearsal_prints_the_cells_start_up_metrics(cell, devices):
    line, out = rehearse(cell, devices=devices, trace=1)
    loaded = spec.load_cell(cell)
    names = check_line(line, loaded, trace=1)
    listed = {m["name"] for m in loaded.per_layer if m["layer"] == "start-up"}
    assert listed == set(SPAN_METRICS + COUNTER_METRICS if devices == 1 else
                         SPAN_METRICS[:2] + COUNTER_METRICS)
    assert listed <= names
    value = {m: line["metrics"][REHEARSAL_PREFIX + m]["value"] for m in listed}
    assert all(v > 0 for m, v in value.items() if m != "setup_cache_misses"), value
    assert value["setup_cache_misses"] >= 0
    if devices == 1:
        # the six span metrics and setup.facts are the whole of the line's own setup_s
        setup_s = float(re.search(r"^set-up (\d+\.\d) s", out, re.M).group(1))
        facts = float(re.search(r"^note: setup\.facts (\d+\.\d+) s", out, re.M).group(1))
        assert facts > 0
        assert abs(sum(value[m] for m in SPAN_METRICS) + facts - setup_s) < 0.2
        assert "note: setup.prewarm, costliest programs: (" in out
