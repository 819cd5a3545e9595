"""CPU rehearsal of the OLMoE closed-loop cell through the benchmark's one
command: the end-to-end line, and the traced line with the metrics that read
the program's routing counters."""

import pytest

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELL = "olmoe-rag-batch"


def test_rag_cell_end_to_end_metrics():
    line, out = rehearse(CELL, trace=0)
    names = check_line(line, spec.load_cell(CELL), trace=0)
    assert names == {"serve_tokens_per_s", "setup_s"}
    assert '"clear_margin": 0.1' in out          # the rehearsal's own check sizes


def test_rag_cell_traced_run_reads_the_routing_counters():
    line, out = rehearse(CELL, trace=1)
    names = check_line(line, spec.load_cell(CELL), trace=1)
    assert {"rag_step_host_self_ms", "rag_expert_useful_flop_share", "rag_expert_load_cv"} <= names
    # device-trace metrics find no device plane on the host and are left out
    for name in ("rag_moe_dev_share", "rag_qk_norm_dev_share", "rag_experts_prefill_roofline",
                 "rag_experts_decode_roofline", "rag_pdecode_dev_p50_ms", "rag_expert_copy_dev_share"):
        assert name not in names and f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    # tiny-olmoe: 2 of 8 experts a token. 4 lanes x 2 = 8 puts decode on the
    # selective path (every computed pair is a lane's), 16-token chunks on
    # all-experts (a quarter is a row's): with every row counted as asking the
    # reading lies strictly between; bucket padding and idle lanes ask for
    # nothing, so the reading itself lies under that
    import re

    note = re.search(r"note: expert pairs asked for by live tokens / computed, by dispatch path: all .*, "
                     r"selective .*; with padding and idle lanes counted as asking: ([0-9.]+) %", out)
    assert note and 25.0 < float(note.group(1)) < 100.0
    assert 0.0 < values["rag_expert_useful_flop_share"] < float(note.group(1))
    assert values["rag_expert_load_cv"] > 0
