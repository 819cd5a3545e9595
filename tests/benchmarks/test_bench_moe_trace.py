"""``benchmarks/moe_trace.py`` on a hand-made trace: the copies of expert
weights outside every block are found by their result type and join the time
under ``moe/experts`` as the experts' cost; a program without any of it reads
as nothing."""

import types

import pytest

from benchmarks import moe_trace, spec
from benchmarks.program_trace import Device, Op

CFG = types.SimpleNamespace(num_experts=64, hidden_size=2048, intermediate_size=1024)
# (name, seconds, op_name path, result type): one pdecode (program 7), one psfx (program 9)
OPS = [
    ("fusion.1", 3.0, "jit(fn)/pdecode/moe/experts/selective/dot_general:", "fusion bf16[64,128,2,1024]", "7"),
    ("fusion.2", 1.0, "jit(fn)/pdecode/attn/sdpa/dot_general:", "fusion bf16[8,16,128]", "7"),
    ("dynamic-slice_bitcast_fusion.1", 2.0, "", "dynamic-slice_bitcast_fusion bf16[64,2048,2,1024]", "7"),
    ("constant_dynamic-update-slice_fusion.3", 1.5, "", "constant_dynamic-update-slice_fusion bf16[8,8,1024,2048]", "7"),
    ("copy.4", 0.5, "", "copy bf16[8,1152,16,16,128]", "7"),                  # the pool: no expert width
    ("fusion.1", 4.0, "jit(fn)/psfx/moe/experts/all/dot_general:", "fusion bf16[64,512,2,1024]", "9"),
    ("copy.2", 0.25, "", "copy bf16[64,1024,2048]", "9"),
]


def result(model_cfg=CFG):
    ops, at = [], 0.0
    for name, dur, path, _, pid in OPS:
        ops.append(Op(name, at, dur, path, pid, ""))
        at += dur
    return {
        "kind": "serving", "model_cfg": model_cfg, "notes": [],
        "reduced": {"devices": [{"ordinal": 0, "busy_s": at}]},
        "program_trace": {"devices": [Device(0, [], ops)], "window": (0.0, at), "steps": []},
        "moe_trace_types": {(pid, name): label for name, _, _, label, pid in OPS},
    }


def test_expert_weights_copied_outside_every_block_are_found_by_their_type():
    r = result()
    assert moe_trace.expert_copy_seconds(r, ("pdecode",)) == {
        "dynamic-slice_bitcast_fusion bf16[64,2048,2,1024]": 2.0,
        "constant_dynamic-update-slice_fusion bf16[8,8,1024,2048]": 1.5,
    }
    assert moe_trace.expert_copy_seconds(r, ("psfx", "pctx")) == {"copy bf16[64,1024,2048]": 0.25}
    assert moe_trace.expert_seconds(r, ("pdecode",)) == pytest.approx(3.0 + 3.5)
    assert moe_trace.expert_seconds(r, ("psfx", "pctx")) == pytest.approx(4.25)
    share = spec.load_metric("layer_metrics", "rag_expert_copy_dev_share")(r)
    assert share == pytest.approx(100 * 3.75 / 12.25)
    assert "copied outside every block" in r["notes"][-1]


def test_a_program_without_scopes_or_experts_reads_as_nothing():
    bare = {"kind": "serving", "model_cfg": CFG, "profile": None, "reduced": None, "notes": []}
    assert moe_trace.expert_copy_seconds(bare, ("pdecode",)) is None
    assert moe_trace.expert_seconds(bare, ("pdecode",)) is None
    assert spec.load_metric("layer_metrics", "rag_expert_copy_dev_share")(bare) is None
    dense = result(types.SimpleNamespace(num_experts=0, hidden_size=2048, intermediate_size=1024))
    assert moe_trace.expert_copy_seconds(dense, ("pdecode",)) is None
