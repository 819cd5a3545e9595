"""The traffic generator: fixed work, a pinned schedule, seeded tokens, due
times on a clock."""

import numpy as np
import pytest

from benchmarks import traffic

CHAT = {
    "arrivals": {"rate_rps": 3.0, "cv": 1.0}, "lead_s": 6.0, "schedule_seed": 0,
    "prompt_tokens": {"dist": "log_uniform", "low": 64, "high": 2048},
    "sharing": {"share": 0.5, "prefixes": 4, "prefix_tokens": 256, "min_own_tokens": 16},
}


def test_arrival_count_is_fixed_and_times_fill_the_horizon():
    a = traffic.arrival_times(CHAT["arrivals"], 46.0, seed=1)
    b = traffic.arrival_times(CHAT["arrivals"], 46.0, seed=2)
    assert len(a) == len(b) == 138            # round(3.0 * 46): the work is fixed
    assert a != b                             # the seed moves the instants
    assert a == sorted(a) and 0.0 < a[0] and a[-1] < 46.0
    assert a == traffic.arrival_times(CHAT["arrivals"], 46.0, seed=1)


def test_burstier_arrivals_keep_the_count():
    calm = np.diff(traffic.arrival_times({"rate_rps": 20, "cv": 1.0}, 100.0, 3))
    bursty = np.diff(traffic.arrival_times({"rate_rps": 20, "cv": 3.0}, 100.0, 3))
    assert len(calm) == len(bursty)
    assert bursty.std() / bursty.mean() > 2.0 > calm.std() / calm.mean() > 0.8


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "log_uniform", "low": 64, "high": 2048}, 64, 2048),
    ({"dist": "log_uniform", "low": 1024, "high": 3008}, 1024, 3008),
    ({"dist": "log_uniform", "low": 8, "high": 48}, 8, 48),
])
def test_length_multiset_sits_inside_its_range(dist, lo, hi):
    lengths = traffic.length_multiset(dist, 101)
    assert len(lengths) == 101 and min(lengths) >= lo and max(lengths) <= hi
    assert lengths == sorted(lengths)
    assert abs(np.median(lengths) - (lo * hi) ** 0.5) < 0.03 * (lo * hi) ** 0.5


def test_a_length_distribution_no_mix_uses_is_refused():
    with pytest.raises(ValueError, match="unknown length distribution"):
        traffic.length_multiset({"dist": "uniform", "low": 1, "high": 2}, 4)


def test_two_schedules_offer_the_same_multiset_in_another_order():
    a = traffic.open_loop(CHAT, 40.0, 32000, seed=1)
    b = traffic.open_loop(dict(CHAT, schedule_seed=1), 40.0, 32000, seed=1)
    la, lb = [len(r.prompt) for r in a], [len(r.prompt) for r in b]
    assert sorted(la) == sorted(lb) and la != lb
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert [r.prompt for r in a] == [r.prompt for r in traffic.open_loop(CHAT, 40.0, 32000, seed=1)]
    assert all(r.due_s is not None for r in a)
    # the window's own segment holds exactly rate x seconds requests, all due
    # inside it, whatever the schedule; lead-in and traced tail are not measured
    for reqs in (a, b):
        inside = [r for r in reqs if r.measured]
        assert len(inside) == 120 and all(6.0 <= r.due_s < 46.0 for r in inside)
        assert all(r.due_s < 6.0 for r in reqs if not r.measured)
    assert sorted(len(r.prompt) for r in a if r.measured) == sorted(len(r.prompt) for r in b if r.measured)
    tail = traffic.open_loop(CHAT, 40.0, 32000, seed=1, tail_s=4.0)
    assert [r.prompt for r in tail[:len(a)]] == [r.prompt for r in a]
    assert len(tail) == len(a) + 12 and all(r.due_s >= 46.0 and not r.measured for r in tail[len(a):])
    assert [r.index for r in tail] == list(range(len(tail)))


def test_the_schedule_is_replayed_with_the_seeds_own_tokens():
    a = traffic.open_loop(CHAT, 40.0, 32000, seed=1, tail_s=4.0)
    b = traffic.open_loop(CHAT, 40.0, 32000, seed=2, tail_s=4.0)
    plan = lambda reqs: [(r.due_s, len(r.prompt), r.prefix_id, r.measured) for r in reqs]  # noqa: E731
    assert plan(a) == plan(b)                                  # who is due when, how long, which prefix
    assert all(x.prompt != y.prompt for x, y in zip(a, b))     # the tokens are the seed's
    with pytest.raises(KeyError, match="schedule_seed"):       # an open loop has to pin one
        traffic.open_loop({k: v for k, v in CHAT.items() if k != "schedule_seed"}, 40.0, 32000, seed=1)


def test_half_the_requests_share_one_of_four_system_prompts():
    reqs = traffic.open_loop(CHAT, 40.0, 32000, seed=5)
    sharers = [r for r in reqs if r.prefix_id is not None]
    assert len(sharers) == len(reqs) // 2
    assert {r.prefix_id for r in sharers} == {0, 1, 2, 3}
    by_prefix = {}
    for r in sharers:
        by_prefix.setdefault(r.prefix_id, set()).add(tuple(r.prompt[:256]))
        assert len(r.prompt) >= 256 + 16
    assert all(len(v) == 1 for v in by_prefix.values())     # really shared
    assert len({next(iter(v)) for v in by_prefix.values()}) == 4
    # sharers are spread over the lengths, not bunched at one end
    assert np.median([len(r.prompt) for r in sharers]) < 3 * np.median([len(r.prompt) for r in reqs])
    assert all(1 <= t < 32000 for r in reqs for t in r.prompt)


def test_closed_loop_pool_and_train_batches():
    docs = {"requests": 12, "prompt_tokens": {"dist": "log_uniform", "low": 24, "high": 52}}
    pool = traffic.closed_loop(docs, 256, seed=0)
    assert len(pool) == 12 and all(r.prefix_id is None and r.due_s is None for r in pool)
    x = traffic.train_batch(4, 16, 100, seed=1, step=0)
    assert x.shape == (4, 16) and x.dtype == np.int32 and x.min() >= 0 and x.max() < 100
    assert (x == traffic.train_batch(4, 16, 100, seed=1, step=0)).all()
    assert (x != traffic.train_batch(4, 16, 100, seed=1, step=1)).any()
    assert (x != traffic.train_batch(4, 16, 100, seed=2, step=0)).any()
