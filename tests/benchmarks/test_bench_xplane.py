"""The trace reduction, on the small trace recorded on a v5e
(``benchmarks/tools/record_tiny_trace.py``: two steps of ``tiny_step`` with a
Pallas kernel ``tiny_double`` and a 5 ms host pause after each) and on
hand-made intervals."""

import os

import pytest

from benchmarks import serving_trace, spec, xplane

TRACE = os.path.join(spec.HERE, "testdata", "tiny_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(TRACE, annotations=("bench_step", "bench_pause"))


def test_recorded_trace_is_small_enough_to_keep():
    assert os.path.getsize(TRACE) < 1 << 20


def test_modules_and_kernels_are_found_by_name(reduced):
    assert len(reduced["devices"]) == 1
    runs = reduced["module_runs"]
    assert len(runs) == 2
    assert {xplane.module_key(n)[0] for n, _, _ in runs} == {"jit_tiny_step"}
    assert len({xplane.module_key(n)[1] for n, _, _ in runs}) == 1      # one fingerprint
    ops = reduced["devices"][0]["ops"]
    assert ops["tiny_double"][0] == 2 and ops["tiny_double"][1] > 0     # the Pallas kernel
    assert ops["convolution_tanh_fusion"][0] == 2
    assert not any(k.endswith("-start") or k.endswith("-done") for k in ops)


def test_busy_idle_and_breakdown(reduced):
    dev = reduced["devices"][0]
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(dev["busy_s"])
    # two ~9 us programs 7 ms apart: the device idles nearly all the window
    assert 15e-6 < dev["busy_s"] < 25e-6 and 0.99 < reduced["idle_share_worst"] < 1.0
    assert dev["busy_s"] <= sum(d for _, _, d in reduced["module_runs"]) + 1e-9
    assert dev["collective_s"] == 0 and dev["collective_exposed_s"] == 0
    names = [n for n, _ in reduced["device_ops"]]
    # ranked by time, labelled by name and result type
    assert names[0] == "convolution_tanh_fusion bf16[1024,512]"
    assert "tiny_double bf16[1024,512]" in names
    assert len(reduced["device_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10
    # the long gap falls in the host's pause, by the benchmark's own span
    assert reduced["idle_gaps"][0][0] == "bench_pause"
    assert reduced["idle_gaps"][0][1] == pytest.approx(reduced["window_s"], rel=0.01)


def test_names():
    assert xplane.op_name("%fusion.12 = bf16[8,128]{1,0} fusion(...)") == "fusion.12"
    assert xplane.op_label("%fusion.12 = bf16[8,128]{1,0} fusion(...)") == "fusion bf16[8,128]"
    assert xplane.op_label("%copy-start.1 = (bf16[4,4]{1,0}, u32[]) copy-start(x)") == "copy-start bf16[4,4]"
    assert xplane.base_name("flash_fwd.3") == "flash_fwd"
    assert xplane.base_name("all-reduce-start.1.2") == "all-reduce-start"
    assert xplane.is_collective("collective-permute-done.4") and not xplane.is_collective("copy-start")
    assert xplane.is_marker("copy-done.1") and not xplane.is_marker("fusion.1")
    assert xplane.module_key("jit_fn(123)") == ("jit_fn", "123")


def test_interval_arithmetic():
    u = xplane.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)] and xplane.total(u) == 4
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert xplane.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert xplane.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]
    assert xplane.clip([(0, 5), (7, 9)], 4, 8) == [(4, 5), (7, 8)]


def test_exposed_collective_time_is_what_compute_does_not_cover():
    E = xplane.Event
    dev = xplane.DeviceTrace(
        ordinal=0,
        modules=[E("jit_train_step(1)", 0.0, 10.0)],
        ops=[
            E("fusion.1", 0.0, 4.0),               # compute
            E("all-reduce-start.1", 1.0, 0.1),     # marker of an async collective
            E("all-reduce-done.1", 4.0, 2.0),      # the wait: nothing else runs
            E("fusion.2", 6.0, 2.0),
            E("collective-permute.1", 8.5, 1.0),   # synchronous, exposed
            E("while.1", 0.0, 10.0),               # a container: not work of its own
        ],
        async_ops=[E("all-reduce-start.1", 1.0, 5.0)],   # in flight 1..6
    )
    r = xplane.reduce_device(dev, 0.0, 10.0)
    assert r["compute_s"] == pytest.approx(6.0)
    assert r["collective_s"] == pytest.approx(6.0)            # 1..6 and 8.5..9.5
    assert r["collective_exposed_s"] == pytest.approx(3.0)    # 4..6 and 8.5..9.5
    assert r["no_compute_in_modules_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx(9.0)                  # idle 8..8.5 and 9.5..10


def _result(runs, steps):
    return {
        "profile": {"engine_steps": steps},
        "reduced": {"module_runs": [(n, float(i), d) for i, (n, d) in enumerate(runs)]},
    }


def test_serving_programs_are_paired_in_order_or_not_at_all():
    steps = [
        {"events": [("X", "prefill_chunk", 0.0, 0.1, {"tokens": 512}),
                    ("X", "lane_sync_flush", 0.1, 0.11, {}),
                    ("X", "dispatch", 0.2, 0.3, {"lanes": 3, "program": "pdecode"})]},
        {"events": [("X", "dispatch", 0.4, 0.5, {"lanes": 4}),
                    ("X", "prefill", 0.6, 0.7, {"tokens": 100})]},
    ]
    runs = [("jit_fn(7)", 0.050), ("jit_fn(9)", 20e-6), ("jit_fn(5)", 0.030),
            ("jit__threefry_split(2)", 0.001), ("jit_fn(5)", 0.031), ("jit_fn(8)", 0.020)]
    kinds, why = serving_trace.classify(_result(runs, steps))
    assert why == "" and kinds["decode"] == [(0.030, 3), (0.031, 4)]
    assert kinds["prefill"] == [(0.050, 512), (0.020, 100)]
    # one execution short: nothing is guessed
    kinds, why = serving_trace.classify(_result(runs[:-1], steps))
    assert kinds is None and "4" in why and "3" in why
    # a fingerprint that lands on both kinds is refused too
    swapped = [runs[2], runs[1], runs[0], runs[3], runs[4], runs[5]]
    kinds, why = serving_trace.classify(_result(swapped, steps))
    assert kinds is None and "both" in why
    assert serving_trace.classify({"profile": None, "reduced": None})[0] is None
