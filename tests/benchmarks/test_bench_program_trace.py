"""``benchmarks/program_trace.py``: the metadata reader on the recorded v5e
trace, the clock join, the scope arithmetic on hand-made ``tf_op`` strings, the
idle split, and the readers' behaviour on a program that has none of it (the
parent of the PR that added them)."""

import json
import os
import random
import types

import jax
import pytest

from benchmarks import program_trace as pt
from benchmarks import spec, xplane

TINY_TRACE = os.path.join(spec.HERE, "testdata", "tiny_trace.xplane.pb")
NEW_METRICS = {
    "mixtral-chat-steady": {
        "door_pre_submit_p50_ms", "door_first_write_p50_ms", "admit_to_first_token_p50_ms",
        "step_host_self_ms", "idle_in_step_share", "idle_between_steps_share",
        "pdecode_dev_p50_ms", "moe_dev_share", "kv_dev_share",
    },
    "mixtral-docs-batch": {"docs_step_host_self_ms", "docs_moe_dev_share"},
    "pythia-train-tp2pp2": {
        "train_attn_dev_share", "train_mlp_dev_share", "train_ce_dev_share",
        "train_optimizer_dev_share", "train_recompute_dev_share", "pipeline_bubble_share",
    },
}


@pytest.fixture(scope="module")
def planes():
    return pt.read(TINY_TRACE)


# ---------------------------------------------------------------------------
# the metadata reader
# ---------------------------------------------------------------------------

def test_reader_gives_tf_op_and_program_id_of_every_op(planes):
    (dev,) = pt.devices_of(planes)
    assert len(dev.modules) == 2 and len(dev.ops) == 16
    fingerprints = {pid for _, pid, _, _ in dev.modules}
    assert len(fingerprints) == 1 and {op.program_id for op in dev.ops} == fingerprints
    by_name = {op.name: op for op in dev.ops}
    kernel = by_name["tiny_double.1"]
    assert "tiny_double" in [inner for _, inner in pt.segments(kernel.tf_op)]
    assert kernel.tf_op == "jit(tiny_step)/tiny_double/pallas_call:"
    assert kernel.category == "custom-call"
    assert by_name["convolution_tanh_fusion"].tf_op == "jit(tiny_step)/dot_general:"
    assert by_name["copy-start"].tf_op == ""          # the compiler's own instruction
    (plane,) = [p for p in planes if p.name == "/device:TPU:0"]
    flops = [s["flops"] for s in plane.event_stats.values() if s.get("hlo_category") == "convolution fusion"]
    assert sorted(flops) == [537919488, 538443776]


def test_reader_agrees_with_profile_data_on_every_event(planes):
    data = jax.profiler.ProfileData.from_file(TINY_TRACE)
    theirs = {}
    for plane in data.planes:
        for line in plane.lines:
            theirs[(plane.name, line.name)] = [
                (ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]
    checked = 0
    for plane in planes:
        for line in plane.lines:
            want = theirs[(plane.name, line.name)]
            assert len(want) == len(line.events)
            for (name, start_ns, dur_ns), (mid, start, dur, _) in zip(want, line.events):
                assert plane.event_names[mid] == name
                assert start * 1e9 == pytest.approx(start_ns, abs=1.0)    # theirs drops the picoseconds
                assert dur * 1e9 == pytest.approx(dur_ns, abs=1.0)
                checked += 1
    assert checked > 100


def test_host_annotations_give_back_their_step(planes):
    seen = pt.annotations_of(planes, "bench_step")
    assert [step for step, _, _ in seen] == [0, 1]
    assert all(1e-3 < b - a < 2e-3 for _, a, b in seen)
    assert pt.annotations_of(planes, "bench_pause") == []      # it carries no `step`
    assert pt.annotations_of(planes, pt.STEP_ANNOTATION) == []


# ---------------------------------------------------------------------------
# the clock join
# ---------------------------------------------------------------------------

def test_clock_join_on_the_recorded_trace(planes):
    seen = pt.annotations_of(planes, "bench_step")
    offset = 1234.5
    steps = [{"step": step, "t0": start - offset} for step, start, _ in seen]
    join = pt.clock_join(seen, steps)
    assert join["offset"] == pytest.approx(offset, abs=1e-9)
    assert join["steps"] == 2 and join["error_us"] < 1e-3


def test_clock_join_recovers_a_known_offset_under_jitter_by_index_not_order():
    rng = random.Random(7)
    offset, n = -98765.4321, 200
    steps = [{"step": 100 + i, "t0": 10.0 + 0.04 * i} for i in range(n)]
    # the annotation opens 2..22 us after t0 was read
    seen = [(s["step"], s["t0"] + offset + rng.uniform(2e-6, 22e-6), 0.0) for s in steps]
    rng.shuffle(seen)                        # order carries nothing
    seen = seen[: n - 30]                    # the profile saw fewer steps than the tracer
    seen.append((9999, 5.0, 0.0))            # and one the tracer's ring no longer holds
    join = pt.clock_join(seen, steps)
    assert join["steps"] == n - 30
    assert join["offset"] == pytest.approx(offset + 12e-6, abs=3e-6)
    assert 5 < join["error_us"] <= 10.5 and join["max_error_us"] <= 12
    assert pt.clock_join([], steps) is None and pt.clock_join(seen, []) is None


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

TRAIN = "jit(train_step)/train_step/shard_map/while/body/closed_call/"


@pytest.mark.parametrize("tf_op,want", [
    ("jit(fn)/pdecode/while/body/attn/kv_write/scatter:", ["pdecode", "attn", "attn/kv_write"]),
    ("jit(fn)/pdecode/while/body/attn_norm/mul:", ["pdecode"]),            # never a substring
    ("jit(fn)/pdecode/normalize/renorm/mul:", ["pdecode"]),
    ("jit(fn)/pdecode/while/body/kv_write/scatter:", ["pdecode"]),          # a child needs its parent
    ("jit(fn)/psfx/moe/router/dot_general:", ["psfx", "moe", "moe/router"]),
    ("jit(fn)/psfx/moe/experts/ech,ehti->ecti/dot_general:", ["psfx", "moe", "moe/experts"]),
    ("jit(fn)/pctx/attn/jit(_take)/qkv/gather:", ["pctx", "attn", "attn/qkv"]),
    ("jit(fn)/pctx/ce:", ["pctx"]),                                         # the last part is the primitive
    (TRAIN + "transpose(jvp(ce))/ce/while/body/closed_call/checkpoint/rematted_computation/lm_head/dot_general:",
     ["train_step", "ce", "lm_head"]),
    (TRAIN + "jvp(embed)/jit(_take)/gather:", ["train_step", "embed"]),
    ("jit(train_step)/train_step/optimizer/grad_clip/grad_clip/mul:", ["train_step", "optimizer", "grad_clip"]),
    ("jit(tiny_step)/tiny_double/pallas_call:", []),
    ("", []),
])
def test_scopes_match_whole_parts_of_the_path(tf_op, want):
    assert pt.scopes_of(tf_op) == tuple(want)


@pytest.mark.parametrize("tf_op,want", [
    (TRAIN + "while/body/closed_call/checkpoint/attn/sdpa/dot_general:", "forward"),
    (TRAIN + "jvp()/while/body/closed_call/attn/qkv/dot_general:", "replay"),
    (TRAIN + "jvp(ce)/ce/while/body/closed_call/lm_head/dot_general:", "replay"),
    (TRAIN + "transpose(jvp())/while/body/closed_call/checkpoint/attn/qkv/dot_general:", "backward"),
    (TRAIN + "transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general:", "recompute"),
    (TRAIN + "transpose(jvp(ce))/ce/while/body/closed_call/checkpoint/rematted_computation/ce/exp:", "recompute"),
    ("jit(fn)/pdecode/attn/sdpa/dot_general:", "forward"),
])
def test_forward_recompute_backward_rule(tf_op, want):
    assert pt.phase_of(tf_op) == want


def _op(name, dur, tf_op, pid="7"):
    return (name, dur, tf_op, pid)


def _device(specs):
    """Ops one after another from t = 0."""
    ops, t = [], 0.0
    for name, dur, tf_op, pid in specs:
        ops.append(pt.Op(name, t, dur, tf_op, pid, ""))
        t += dur
    return pt.Device(0, [], ops)


def _one_f_one_b_device(replay=1.0):
    return _device([
        _op("fusion.1", 2.0, TRAIN + "while/body/closed_call/checkpoint/attn/qkv/dot_general:"),
        _op("fusion.2", replay, TRAIN + "jvp()/while/body/closed_call/attn/qkv/dot_general:"),
        _op("flash_fwd", 1.0, TRAIN + "transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn/sdpa/pallas_call:"),
        _op("fusion.3", 3.0, TRAIN + "transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general:"),
        _op("fusion.4", 0.5, TRAIN + "transpose(jvp(ce))/ce/while/body/closed_call/checkpoint/lm_head/dot_general:"),
        _op("fusion.6", 0.25, TRAIN + "jvp(ce)/ce/while/body/closed_call/lm_head/dot_general:"),
        _op("fusion.5", 1.5, "jit(train_step)/train_step/optimizer/grad_clip/mul:"),
        _op("copy.1", 1.0, ""),                                  # the compiler's: booked to the root
        _op("while.1", 100.0, TRAIN + "while:"),                 # a container: its children's time
        _op("all-gather-start.1", 0.25, ""),                     # a collective's marker counts
        _op("copy-start.2", 9.0, ""),                            # another marker does not
        _op("fusion.9", 0.75, "jit(other)/dot_general:", pid="8"),   # a program with no scope
    ])


def test_scope_seconds_book_each_op_once():
    dev = _one_f_one_b_device()
    sec = pt.device_scope_seconds(dev, -1.0, 1e9)
    under = lambda *a, **k: pt.seconds_under(sec, *a, **k)          # noqa: E731
    assert sec["unscoped_s"] == 0.75
    assert under(None) == 2 + 1 + 1 + 3 + .5 + .25 + 1.5 + 1 + .25    # every scoped op, once
    assert under(("train_step",)) == under(None)                    # the root holds them all
    assert under(("attn",)) == 4.0 and under(("attn/qkv",)) == 3.0 and under(("attn/sdpa",)) == 1.0
    assert under(("ce", "lm_head")) == 0.75                         # under both: counted once
    assert under(("optimizer", "grad_clip")) == 1.5
    assert under(("mlp",), ("backward",)) == 3.0 and under(("mlp",), ("forward",)) == 0.0
    # attn runs outside any jvp twice as long as inside one: the jvp pass replays
    # it; the head (ce) only ever runs inside its VJP, so its jvp pass is its forward
    assert pt.replayed_blocks(sec) == {"attn"}
    assert pt.recompute_seconds(sec) == 1.0 + 1.0               # the replay + the remat
    # a window cuts whole ops
    assert pt.seconds_under(pt.device_scope_seconds(dev, 2.5, 3.5), None) == 1.0 + 1.0
    assert under(None, ("replay",)) == 1.25


def test_under_plain_autodiff_the_linearisation_is_the_forward():
    dev = _device([
        _op("fusion.1", 0.01, "jit(train_step)/attn/rope/jit(_take)/gather:"),      # hoisted by XLA
        _op("fusion.2", 2.0, "jit(train_step)/train_step/jvp()/while/body/closed_call/attn/qkv/dot_general:"),
        _op("fusion.3", 4.0, "jit(train_step)/train_step/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general:"),
    ])
    sec = pt.device_scope_seconds(dev, -1.0, 1e9)
    assert pt.replayed_blocks(sec) == set() and pt.recompute_seconds(sec) == 0.0
    assert sec["unscoped_s"] == 0.0            # booked to the root its program_id names


def test_scope_shares_and_program_runs_from_a_result():
    dev = _one_f_one_b_device()
    dev.modules = [("jit_train_step(7)", "7", 0.0, 5.0), ("jit_train_step(7)", "7", 6.0, 7.0),
                   ("jit_other(8)", "8", 20.0, 0.75)]
    result = {
        "notes": [], "reduced": {"devices": [{"ordinal": 0, "busy_s": 20.0}]},
        "program_trace": {"devices": [dev], "window": (-1.0, 1e9), "steps": []},
    }
    shares = pt.scope_shares(result)
    assert pt.mean_share(shares, ("attn",)) == pytest.approx(100 * 4.0 / 20.0)
    assert pt.recompute_seconds(shares["devices"][0]) == 2.0
    assert pt.program_run_ms(result, "train_step") == [5000.0, 7000.0]
    assert pt.program_run_ms(result, "pdecode") is None
    notes = "\n".join(result["notes"])
    assert "unscoped 3.75" in notes and "a second run of attn (they" in notes
    assert pt.idle_split(result) is None                 # no graft.step in this trace


def test_vocabulary_is_the_programs():
    from neuronx_distributed_llama3_2_tpu.serving import tracing

    assert pt.SCOPES == tracing.SCOPES and pt.STEP_ANNOTATION == tracing.STEP_ANNOTATION
    assert pt.CHILD_SCOPES == tracing.CHILD_SCOPES and pt.PROGRAM_SCOPES == tracing.PROGRAM_SCOPES


# ---------------------------------------------------------------------------
# host spans: self time, idle split
# ---------------------------------------------------------------------------

def test_step_self_time_is_the_step_less_its_childrens_cover():
    step = {"step": 1, "t0": 10.0, "t1": 10.050, "events": [
        ("X", "admit", 10.001, 10.021, {}),
        ("X", "prefill", 10.002, 10.020, {}),              # inside admit: only prefill is a child
        ("X", "dispatch", 10.022, 10.030, {}),
        ("X", "readback", 10.029, 10.045, {}),             # overlaps dispatch: the union counts
        ("X", "table_delta_flush", 10.046, 10.048, {}),
        ("i", "fault", 10.01, None, {}),
    ]}
    assert pt.step_self_ms(step) == pytest.approx(50.0 - 18.0 - 23.0)


def test_idle_gaps_go_to_the_innermost_span():
    spans = [(0.0, 10.0, "admit"), (2.0, 4.0, "prefill"), (12.0, 13.0, "drive.yield")]
    split = pt._innermost_split([(1.0, 5.0), (9.0, 12.5)], spans, "step (self)")
    assert split == pytest.approx({"admit": 1 + 1 + 1, "prefill": 2.0, "step (self)": 2.0,
                                   "drive.yield": 0.5})


def test_idle_split_adds_up_to_the_idle_share():
    busy = [(0.0, 1.0), (1.5, 3.0), (3.2, 4.0), (6.0, 10.0)]          # idle 0.5 + 0.2 + 2.0
    result = {
        "notes": [], "kind": "serving",
        "reduced": {"devices": [{"ordinal": 0, "busy_s": 7.3, "busy": busy}]},
        "program_trace": {"devices": [], "window": (0.0, 10.0),
                          "steps": [(1, 0.9, 1.6), (2, 3.1, 3.9), (3, 5.0, 6.5)]},
    }
    split = pt.idle_split(result)
    assert split["in_step"] == pytest.approx(100 * (0.5 + 0.1 + 1.0) / 10.0)
    assert split["between"] == pytest.approx(100 * (0.1 + 1.0) / 10.0)
    assert split["in_step"] + split["between"] == pytest.approx(100 * (1 - 7.3 / 10.0))
    assert "shorter than the 1 ms" in "\n".join(result["notes"])


# ---------------------------------------------------------------------------
# the new entries, and a program without any of it
# ---------------------------------------------------------------------------

def test_every_new_entry_has_a_reader_and_sits_at_the_end():
    with open(os.path.join(spec.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    new = set().union(*NEW_METRICS.values())
    assert set(names[-len(new):]) == new and names[17] == "train_device_idle_share"
    for cell, metrics in NEW_METRICS.items():
        reported = {m["name"] for m in spec.load_cell(cell).per_layer}
        assert metrics <= reported
        for name in metrics:
            assert callable(spec.load_metric("layer_metrics", name))
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert sources["pipeline_bubble_share"] == "program_counter"
    assert sources["door_pre_submit_p50_ms"] == sources["step_host_self_ms"] == "program_span"


@pytest.mark.parametrize("kind", ["serving", "training"])
def test_on_a_program_without_spans_scopes_or_counters_every_reader_returns_nothing(kind, monkeypatch):
    """What the driver's traced run of the parent commit meets: an engine whose
    tracer has no ``timeline``, a trace without scopes or ``graft.step``, no
    ``COMPILED_SCHEDULES``. Nothing raises; every metric is left out."""
    from neuronx_distributed_llama3_2_tpu.pipeline import model as pipeline_model

    monkeypatch.delattr(pipeline_model, "COMPILED_SCHEDULES")
    old_tracer = types.SimpleNamespace(enabled=True, _steps=[])
    reduced = xplane.reduce(TINY_TRACE)
    result = {
        "kind": kind, "serving": types.SimpleNamespace(tracer=old_tracer), "window": (0.0, 1.0),
        "in_window": [], "notes": [], "profile": {"xplane": TINY_TRACE, "engine_steps": []},
        "reduced": reduced,
    }
    for name in set().union(*NEW_METRICS.values()):
        assert spec.load_metric("layer_metrics", name)(result) is None, name
    # ... and with no profile at all (a rehearsal's device metrics)
    bare = {"kind": kind, "serving": None, "window": (0.0, 1.0), "notes": [], "profile": None,
            "reduced": None}
    for name in set().union(*NEW_METRICS.values()) - {"pipeline_bubble_share"}:
        assert spec.load_metric("layer_metrics", name)(bare) is None, name
