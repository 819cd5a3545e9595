"""Compile every Pallas kernel for a TPU v5e on a host that has no chip.

libtpu can describe a topology and compile for it without owning a device
(``jax.experimental.topologies``), so the whole of Mosaic — not just the
Pallas lowering tier-1 checks in ``tests/test_chip_lowering.py`` — runs here
in the sandbox and costs no chip time. This script pushes that test's case
matrix through ``.lower().compile()`` against a ``v5e:2x2`` topology and
prints one ``[ok]``/``[FAIL]`` line per case with the compiler's words.

It proves a kernel *compiles*; whether it computes the right thing is still
``scripts/tpu_kernel_gate.py`` on the chip.

Usage: ``python scripts/tpu_aot_compile.py [substring ...]`` — exit 0 when
every selected case compiled.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# the process itself stays on the CPU; libtpu is only asked to compile.
# Without a TPU VM's metadata it needs to be told what it is compiling for.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["NXDT_KERNEL_MODE"] = "compiled"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402


def _load_matrix():
    spec = importlib.util.spec_from_file_location(
        "chip_lowering_cases",
        os.path.join(REPO_ROOT, "tests", "test_chip_lowering.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv) -> int:
    cases = _load_matrix()
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices
    print(f"compiling for {len(devices)} x {devices[0].device_kind}, "
          f"jax {jax.__version__}")
    one = NamedSharding(Mesh(devices[:1], ("x",)), PartitionSpec())

    from neuronx_distributed_llama3_2_tpu.parallel import state as ps

    tp_state = ps.initialize_model_parallel(
        tensor_model_parallel_size=4, devices=devices
    )
    replicated = NamedSharding(tp_state.mesh, PartitionSpec())

    jobs = []
    for name, args in cases.FLASH_CASES.items():
        jobs.append((name, cases.flash_case(*args), one))
    for name, kw in cases.PAGED_CASES.items():
        jobs.append((name, cases.paged_case(**kw), one))
    for name, dtype in cases.RETENTION_CASES.items():
        jobs.append((name, cases.retention_case(dtype), one))
    for name, args in cases.SSM_SCAN_CASES.items():
        jobs.append((name, cases.ssm_scan_case(*args), one))
    for name, args in cases.SSM_STEP_CASES.items():
        jobs.append((name, cases.ssm_step_case(*args), one))
    for name, args in cases.SPARSE_CHUNK_CASES.items():
        jobs.append((name, cases.sparse_chunk_case(*args), one))
    for name, (dtype, shape) in cases.WALK_CASES.items():
        jobs.append((name, cases.walk_case(dtype, shape), one))
        # every group size of scripts/paged_decode_bench.py --walk
        for group in (8, 16, 32, 64):
            jobs.append((f"{name}-group{group}", cases.walk_case(dtype, shape, group), one))
    for name, (dtype, shape) in cases.LATENT_WALK_CASES.items():
        jobs.append((name, cases.latent_walk_case(dtype, shape), one))
        # every other group size of scripts/paged_decode_bench.py --latent
        for group in (16, 32, 64, 256):
            jobs.append((f"{name}-group{group}", cases.latent_walk_case(dtype, shape, group), one))
    for name, kw in cases.TP_CASES.items():
        jobs.append(
            (name, cases.paged_case(mesh=tp_state.mesh, **kw), replicated)
        )

    for name, (mesh_kw, impl) in cases.RING_CASES.items():
        ps.destroy_model_parallel()
        mesh = ps.initialize_model_parallel(devices=devices, **mesh_kw).mesh
        jobs.append((name, cases.ring_case(mesh, impl),
                     NamedSharding(mesh, PartitionSpec())))

    failed = 0
    for name, (fn, avals), sharding in jobs:
        if argv and not any(s in name for s in argv):
            continue
        avals = [
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in avals
        ]
        t0 = time.perf_counter()
        try:
            jax.jit(fn).trace(*avals).lower().compile()
        except Exception as e:  # report the compiler's words, keep going
            failed += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {e}"[:2000], flush=True)
        else:
            print(f"[ok]   {name} ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
    print("tpu_aot_compile:", "PASS" if not failed else f"{failed} FAILED")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
