"""The rules that keep a run honest about its device: where compiled
programs are cached, which peaks numbers are normalised by, how kernels
run, and that the chip entry points refuse a host without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from neuronx_distributed_llama3_2_tpu import flops
from neuronx_distributed_llama3_2_tpu.kernels import mode
from neuronx_distributed_llama3_2_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache ----------------------------------------------------------

# named scopes live in HLO metadata, which JAX's default cache key ignores
METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv(runtime.COMPILE_CACHE_ENV, "/x")
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    assert runtime.enable_compile_cache() == "/x"
    assert updates == [METADATA_IN_KEY]       # the directory is never touched


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(runtime.COMPILE_CACHE_ENV, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.enable_compile_cache() == want
    assert runtime.enable_compile_cache() == want  # no pid, no timestamp
    assert updates == [METADATA_IN_KEY, ("jax_compilation_cache_dir", want)] * 2


# -- peaks ------------------------------------------------------------------

def test_peaks_unknown_tpu_kind_is_an_error():
    with pytest.raises(ValueError, match="not in flops.CHIP_PEAKS"):
        flops.chip_peaks("TPU v99")


def test_peaks_rows_carry_a_source_and_the_cpu_tier_names_its_row():
    for kind, row in flops.CHIP_PEAKS.items():
        assert row.source, kind
    assert flops.chip_peaks() is flops.CHIP_PEAKS[flops.CPU_BORROWED_KIND]
    v5e = flops.chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)


# -- kernel mode ------------------------------------------------------------

def test_kernel_mode_is_asked_for_by_name(monkeypatch):
    for name, interpret, pallas in (
        ("compiled", False, True),
        ("interpret", True, True),
        ("reference", True, False),
    ):
        monkeypatch.setenv(mode.KERNEL_MODE_ENV, name)
        assert mode.kernel_mode() == name
        assert mode.pallas_interpret() is interpret
        assert mode.prefer_pallas() is pallas
    monkeypatch.setenv(mode.KERNEL_MODE_ENV, "fast")
    with pytest.raises(ValueError, match="expected one of"):
        mode.kernel_mode()


def test_kernel_mode_unasked_off_tpu_is_an_error(monkeypatch):
    """This process runs on the CPU: with nothing asked for there is no mode
    to fall back to — the silent miss the old default_backend() tests hid."""
    monkeypatch.delenv(mode.KERNEL_MODE_ENV, raising=False)
    with pytest.raises(RuntimeError, match="no kernel mode for platform 'cpu'"):
        mode.kernel_mode()


# -- the chip entry points refuse a host without a TPU ----------------------

@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_chip_entry_points_exit_nonzero_without_a_tpu(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    # refused before any work: no result line, no step
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout
    assert "leg " not in proc.stdout


def test_chip_smoke_last_line_is_ok_and_device_only():
    """The chip check reads the last stdout line and refuses any other key
    (PR 21's first submission carried the per-leg results there)."""
    import json

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


# -- a kernel the compiler refuses stops the engine at construction ---------

def test_refused_kernel_variant_fails_engine_construction(monkeypatch):
    """Eligibility (``_paged_kernel_eligible``) never asks the compiler, and
    a prewarming constructor (what every cell and a server build) compiles
    the decode programs: a variant Mosaic refuses raises there, in the
    compiler's words — it cannot fall through to the gather path and serve."""
    import dataclasses

    from neuronx_distributed_llama3_2_tpu.inference import (
        GenerationConfig,
        InferenceEngine,
    )
    from neuronx_distributed_llama3_2_tpu.kernels import paged_attention_pallas
    from neuronx_distributed_llama3_2_tpu.models.llama import (
        LLAMA_CONFIGS,
        LlamaForCausalLM,
    )
    from neuronx_distributed_llama3_2_tpu.serving import (
        PagedConfig,
        PagedServingEngine,
    )

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel: unsupported")

    monkeypatch.setattr(paged_attention_pallas, "paged_flash_decode", refuse)
    cfg = dataclasses.replace(LLAMA_CONFIGS["tiny"], use_paged_kernel=True)
    params = LlamaForCausalLM(cfg).init(jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_batch=2, max_seq_len=64)
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        PagedServingEngine(engine, GenerationConfig(max_new_tokens=2),
                           PagedConfig(num_blocks=16, prewarm=True))
