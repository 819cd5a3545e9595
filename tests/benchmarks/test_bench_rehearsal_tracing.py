"""The traced CPU rehearsal of each cell prints the per-layer metrics that read
the program's own spans and counters (PR 23). Those that read a device trace
find no device plane on the host, return nothing and are left out — the same
path the driver's traced run of a parent without scopes takes."""

import pytest

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec

CELLS = {
    "mixtral-chat-steady": (1, {
        "door_pre_submit_p50_ms", "door_first_write_p50_ms", "admit_to_first_token_p50_ms",
        "step_host_self_ms",
    }, {"idle_in_step_share", "idle_between_steps_share", "pdecode_dev_p50_ms", "moe_dev_share",
        "kv_dev_share"}),
    "mixtral-docs-batch": (1, {"docs_step_host_self_ms"}, {"docs_moe_dev_share"}),
    "pythia-train-tp2pp2": (4, {"pipeline_bubble_share"}, {
        "train_attn_dev_share", "train_mlp_dev_share", "train_ce_dev_share",
        "train_optimizer_dev_share", "train_recompute_dev_share"}),
}


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_traced_rehearsal_prints_the_program_span_and_counter_metrics(workload):
    devices, printed, left_out = CELLS[workload]
    line, out = rehearse(workload, devices=devices, trace=1)
    names = check_line(line, spec.load_cell(workload), trace=1)
    assert printed <= names, printed - names
    assert not left_out & names
    for name in left_out:
        assert f"note: {name}: nothing to read, left out" in out
    values = {k.split(".", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    if workload == "pythia-train-tp2pp2":
        # the rehearsal's schedule: 4 micro-batches over 2 stages, 6 rotations
        assert values["pipeline_bubble_share"] == pytest.approx(100 * (1 - 8 / 12))
    else:
        assert all(values[n] > 0 for n in printed)
    if workload == "mixtral-chat-steady":
        assert "note: ttft legs (p50 ms, " in out
