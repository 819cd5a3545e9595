"""Test config: force an 8-device virtual CPU backend before jax import.

Mirrors the reference's hardware-free unit-test tier (SURVEY.md §4): schedules
and partition logic test pure; distributed numerics test on a multi-device CPU
mesh (the analogue of the reference's mocked process groups +
single-XLA-device golden comparisons, test/unit_test/...).
"""

import os

import jax

# the kernel mode is something a caller asks for (kernels/mode.py): this
# tier runs on the host, so it names the jnp references (and the Pallas
# interpreter where a kernel is called directly). Child processes inherit it.
os.environ.setdefault("NXDT_KERNEL_MODE", "reference")

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)

# Persistent XLA compile cache: the suite is compile-dominated (hundreds of
# tiny jit programs, identical across runs), and warm-cache runs cut wall
# time several-fold (measured 1.3s -> 0.18s per program). Keyed by HLO +
# compile options, so staleness is not a correctness risk; placed by
# JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
# (JAX_ENABLE_COMPILATION_CACHE=false gives a cold-compile tier). The
# cpu_aot_loader "machine feature +prefer-no-scatter" E-spam on cache hits
# is an XLA tuning-flag-vs-CPUID cosmetic mismatch, captured away by pytest.
from neuronx_distributed_llama3_2_tpu.utils.runtime import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
# no floor on compile time: a tiny engine's programs compile in ~0.2 s each and
# every test jits its own, so under a floor the tests of one file compiled the
# same programs again and again — the driver's run starts from an empty cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import pytest  # noqa: E402

from neuronx_distributed_llama3_2_tpu.parallel import state as parallel_state  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    yield
    parallel_state.destroy_model_parallel()
