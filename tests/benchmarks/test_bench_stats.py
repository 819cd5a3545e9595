"""Percentiles over raw samples, and the client-side metric arithmetic."""

import importlib

import numpy as np
import pytest

from benchmarks import serving, spec, stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
def test_percentile_matches_linear_interpolation(q):
    xs = np.random.default_rng(q).exponential(size=137).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q), rel=1e-12)


def test_percentile_edges():
    assert stats.percentile([], 50) is None
    assert stats.percentile([4.0], 90) == 4.0
    assert stats.median([1, 2, 3, 10]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


@pytest.mark.parametrize("n,want", [(5, None), (20, 50.0), (100, 90.0), (250, 95.0), (1000, 99.0)])
def test_highest_supported_percentile(n, want):
    assert stats.highest_supported_percentile(n) == want


def _sample(i, due, sent, first, last, done, tokens, prompt=100, **kw):
    return serving.Sample(index=i, prompt_tokens=prompt, prefix_id=None, due=due, sent=sent,
                          first_token=first, last_token=last, done=done, tokens=tokens, **kw)


def test_ttft_counts_from_due_and_tpot_from_first_token():
    s = _sample(0, due=10.0, sent=10.03, first=10.25, last=11.25, done=11.26, tokens=11)
    assert serving.ttft_ms([s]) == [pytest.approx(250.0)]          # not 220: due, not sent
    assert serving.tpot_ms([s]) == [pytest.approx(100.0)]
    unfinished = _sample(1, 10.0, 10.0, 10.1, 10.2, None, 3)
    assert serving.tpot_ms([unfinished]) == [] and len(serving.ttft_ms([unfinished])) == 1
    failed = _sample(2, 10.0, 10.0, 10.1, 10.2, 10.3, 3, error="boom")
    assert serving.tpot_ms([failed]) == [] and len(serving.ttft_ms([failed])) == 1


def test_tokens_per_s_credits_by_overlap_with_the_window():
    window = (100.0, 110.0)
    inside = _sample(0, 101, 101.0, 101.5, 102.9, 103.0, 10, prompt=990)     # 1000 tokens, whole
    straddles = _sample(1, 99, 99.0, 99.5, 100.9, 101.0, 10, prompt=990)    # half inside
    late = _sample(2, 109, 109.0, 109.5, 112.9, 113.0, 10, prompt=990)      # a quarter inside
    outside = _sample(3, 90, 90.0, 90.5, 91.0, 92.0, 10, prompt=990)
    open_ = _sample(4, 105, 105.0, 105.5, 106.0, None, 5, prompt=990)
    got = serving.tokens_per_s([inside, straddles, late, outside, open_], window)
    assert got == pytest.approx((1000 + 500 + 250) / 10.0)


def test_generator_lateness_and_hit_rate_readers():
    late = spec.load_metric("layer_metrics", "gen_late_p90_ms")
    hit = spec.load_metric("layer_metrics", "prefix_hit_rate")
    samples = [
        _sample(i, due=float(i), sent=i + 0.001 * i, first=i + 0.1, last=i + 0.2,
                done=i + 0.3, tokens=4, cached_tokens=25 if i % 2 else 0)
        for i in range(11)
    ]
    result = {"kind": "serving", "in_window": samples}
    assert late(result) == pytest.approx(9.0)        # p90 of 0..10 ms
    assert hit(result) == pytest.approx(100.0 * 5 * 25 / (11 * 100))
    assert late({"kind": "training"}) is None


def test_every_metric_in_benchmark_json_has_a_reader():
    import json
    import os

    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for kind, rows in (("end_to_end", bench["end_to_end"]), ("layer_metrics", bench["per_layer"])):
        for row in rows:
            assert callable(spec.load_metric(kind, row["name"])), row["name"]
    assert importlib.import_module("benchmarks.layer_metrics.device_idle_share").read({}) is None
