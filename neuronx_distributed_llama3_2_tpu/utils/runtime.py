"""Process-level JAX set-up shared by the entry points (``chip_smoke.py``,
``bench.py``, ``examples/``, ``scripts/``, ``tests/conftest.py``): where
compiled programs are cached, which device the process must find, and the
virtual CPU mesh the host-side tiers run on."""

from __future__ import annotations

import importlib
import os
import threading

import jax

from neuronx_distributed_llama3_2_tpu.utils.setup_record import SETUP

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — fixed, because the directory is part of the cache
# key: a path built from tempfile, a pid or a timestamp never hits
_DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is touched — the cache can be placed from outside. Otherwise the
    cache goes to the fixed ``<checkout>/.jax_cache``. This is the only
    place in the repo that sets ``jax_compilation_cache_dir``.

    The cache key includes the HLO metadata: ``jax.named_scope`` names live
    there (``op_name``), and with JAX's default key a program cached before a
    scope was added is served in place of the scoped one — same instructions,
    old names, and a device trace that cannot be told apart by scope."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_COMPILE_CACHE)
    return _DEFAULT_COMPILE_CACHE


_KERNEL_STACK = "jax.experimental.pallas.tpu"


def _import_quietly(name: str) -> None:
    try:
        importlib.import_module(name)
    except Exception:       # whoever needs the module raises it where it matters
        pass


def require_tpu() -> dict:
    """The device the process runs on, as JAX reports it — or an error when
    that is not a TPU. Entry points that produce device numbers call this
    before any work: when libtpu fails to start, JAX falls back to the CPU
    with a warning, and a benchmark that carried on would time the host."""
    device = device_summary()
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found platform {device['platform']!r} "
            f"({device['kind']}); this entry point measures the chip and "
            "does not fall back to the host"
        )
    return device


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of this process's devices, as JAX
    reports them — what every result line names. The first call is the
    backend's initialisation (seconds on a TPU): the ``setup.runtime`` span of
    the process's set-up record (``utils/setup_record.py``)."""
    if any(span[0] == "setup.runtime" for span in SETUP.spans):
        devices = jax.devices()
    else:
        with SETUP.span("setup.runtime"):
            # the runtime's start leaves the interpreter idle for 5-11 s on a
            # TPU (PERF.md section 5); the Pallas stack is 1.2 s of imports
            # that the first program holding a kernel would pay inside set-up
            # after it: import it beside. Not joined — an import that needs
            # it waits on the module's own lock
            threading.Thread(target=_import_quietly, args=(_KERNEL_STACK,), daemon=True).start()
            devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def set_cpu_devices(n: int) -> None:
    """Force an ``n``-device virtual CPU backend. Must run before the
    backend initializes (first ``devices()`` / first compile). Asking for
    the host is also asking for a host kernel mode (:mod:`..kernels.mode`):
    ``"reference"`` unless the caller already named another."""
    os.environ.setdefault("NXDT_KERNEL_MODE", "reference")
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
