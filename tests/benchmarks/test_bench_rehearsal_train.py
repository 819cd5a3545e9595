"""CPU rehearsal of the training cell on four virtual devices (tp 2 x pp 2,
1F1B), untraced and traced."""

from bench_rehearsal_util import check_line, rehearse
from benchmarks import spec


def test_train_cell_end_to_end_metrics():
    line, out = rehearse("pythia-train-tp2pp2", devices=4, trace=0)
    names = check_line(line, spec.load_cell("pythia-train-tp2pp2"), trace=0)
    assert names == {"train_tokens_per_s_per_chip", "setup_s"}
    assert line["device"]["count"] == 4
    assert '"rel_error"' in out          # the step-0 loss met the plain reference


def test_train_cell_traced_run():
    line, _ = rehearse("pythia-train-tp2pp2", devices=4, trace=1)
    names = check_line(line, spec.load_cell("pythia-train-tp2pp2"), trace=1)
    assert "step_p50_ms" in names
    # utilization needs a chip's peak and a device trace: not on the CPU
    assert not names & {"train_mfu", "flash_time_share", "train_device_idle_share"}
