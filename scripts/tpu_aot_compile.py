"""Compile every Pallas kernel for a TPU v5e on a host that has no chip.

libtpu can describe a topology and compile for it without owning a device
(``jax.experimental.topologies``), so the whole of Mosaic — not just the
Pallas lowering tier-1 checks in ``tests/test_chip_lowering.py`` — runs here
in the sandbox and costs no chip time. This script pushes that test's case
matrix through ``.lower().compile()`` against a ``v5e:2x2`` topology and
prints one ``[ok]``/``[FAIL]`` line per case with the compiler's words.

It proves a kernel *compiles*; whether it computes the right thing is still
``scripts/tpu_kernel_gate.py`` on the chip.

One entry is whole programs, not a kernel: ``smallthinker`` compiles
``smallthinker-longchat-steady``'s real ``pdecode`` and ``psfx`` ladder (the
cell's own sizes, read from ``benchmarks/``) and prints, a program, the bulk
moves of either pool's shape it holds (none: a pool of 4 kv heads rests in
half-tiles, and a gather that asked for ``(bs, NKV, D)`` slices re-tiled the
whole pool a layer a call), its ``temp_size_in_bytes`` and, for a ``pdecode``,
the gathers of every lane's whole ring it holds (none: a window layer's one
row a lane is ``paged_decode_walk``'s) beside its count of walk calls. A
program that holds such a move or gather is a ``[FAIL]``. It builds a 4.7-GB model's abstract weights,
so it runs only where a substring names it (≈ 45 s for the eight programs).

Usage: ``python scripts/tpu_aot_compile.py [substring ...]`` — exit 0 when
every selected case compiled.
"""

from __future__ import annotations

import importlib.util
import os
import re
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


WALK_CALL = re.compile(r"custom_call_target=\"tpu_custom_call\".*paged_decode_walk")


def _load_test(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO_ROOT, "tests", name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_programs(workload, device):
    """(name, compile) for each ``pdecode`` and ``psfx`` the paged engine
    builds for a cell whose stack keeps a ring a lane beside a pool (the
    engine's own sizing: ring = window - 1 + the chunk in whole blocks, 1 null
    block + lanes x ring a window layer), abstract weights and pools on
    ``device``. ``compile()`` reports the program's bulk moves of a pool's
    shape and its temporaries, and returns the moves."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks import spec
    from neuronx_distributed_llama3_2_tpu.inference.model import decode_model_for
    from neuronx_distributed_llama3_2_tpu.parallel import state as ps

    placement = _load_test("test_weight_placement")
    cell = spec.load_cell(workload)
    family = spec.load_family(cell.config["family"])
    sizes = cell.traffic["engine"]
    lanes, bs, top = sizes["lanes"], sizes["block_size"], sizes["max_seq_len"]
    chunk = sizes["prefill_chunk_tokens"]
    cfg = family.model_config(cell.config, False, max_seq_len=top)
    model = decode_model_for(cfg)
    window = next(kind for kind in model.cache_kinds if kind.rows is not None)
    ring = -(-(window.rows - 1 + chunk) // bs)
    one = SingleDeviceSharding(device)
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    params = on(jax.eval_shape(family.train_model(cfg).init, jax.random.key(0)))
    cache = on(jax.eval_shape(lambda: model.init_paged_cache(
        sizes["pool_blocks"], bs, window_blocks=1 + lanes * ring)))
    dims = placement.pool_dims(cache.full.k.shape, cache.window.k.shape)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    width = -(-top // bs) + -(-chunk // bs)
    head = model._model()._logits

    def report(fn, donate, *args, rings_of=None):
        ps.destroy_model_parallel()     # one chip: the kernel cases' meshes are not this program's
        compiled = jax.jit(fn, donate_argnums=donate).lower(params, cache, *args).compile()
        text = compiled.as_text()
        moves = placement.pool_sized_moves(text, dims)
        said = (f"       temp_size_in_bytes {compiled.memory_analysis().temp_size_in_bytes:,}; "
                f"bulk moves of a pool's shape: {moves or 'none'}")
        if rings_of is not None:
            # a decode step walks a window layer's ring: no gather of every lane's
            gathers = placement.ring_gathers(text, rings_of, ring, cfg.num_kv_heads, bs)
            said += (f"; gathers of the lanes' rings: {gathers or 'none'}; "
                     f"paged_decode_walk calls: {len(WALK_CALL.findall(text))}")
            moves = moves + gathers
        print(said, flush=True)
        return moves

    for kv in sizes["kv_buckets"]:
        def psfx(params, cache, ids, start, length, table, rings, kv=kv):
            hidden, cache = model.forward(
                params, cache, ids, start, None, return_hidden=True,
                block_tables=table, kv_limit=kv, window_tables=rings)
            row = jnp.take_along_axis(hidden, (length - 1)[:, None, None], axis=1)
            return jnp.argmax(head(params, row)[:, 0], -1), cache

        def pdecode(params, cache, tokens, positions, tables, rings, kv=kv):
            logits, positions, cache = model.decode_step(
                params, cache, tokens, positions, tables, kv_limit=kv, window_tables=rings)
            return jnp.argmax(logits, -1), positions, cache

        yield f"smallthinker-psfx[{chunk},kv={kv}]", lambda fn=psfx: report(
            fn, 1, i32(1, chunk), i32(1), i32(1), i32(1, width), i32(1, ring))
        yield f"smallthinker-pdecode[kv={kv}]", lambda fn=pdecode: report(
            fn, (1, 3), i32(lanes), i32(lanes), i32(lanes, width), i32(lanes, ring), rings_of=lanes)


def main(argv) -> int:
    from scripts.paged_decode_bench import window_sweep_groups

    cases = _load_test("test_chip_lowering")
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
        nkv, window = cases.WALK_SHAPES[shape][2], cases.WALK_SHAPES[shape][-1]
        for group in (8, 16, 32, 64) if window is None else window_sweep_groups(window, 16, nkv):
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

    def kernel(fn, avals, sharding):
        def compile_():
            jax.jit(fn).trace(*(
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in avals
            )).lower().compile()
        return compile_

    compiles = [(name, kernel(fn, avals, sharding)) for name, (fn, avals), sharding in jobs]
    if any("smallthinker" in s for s in argv):
        compiles += cell_programs("smallthinker-longchat-steady", devices[0])

    failed = 0
    for name, compile_ in compiles:
        if argv and not any(s in name for s in argv):
            continue
        t0 = time.perf_counter()
        try:
            moves = compile_()
        except Exception as e:  # report the compiler's words, keep going
            failed += 1
            print(f"[FAIL] {name}: {type(e).__name__}: {e}"[:2000], flush=True)
        else:
            failed += bool(moves)
            print(f"{'[FAIL]' if moves else '[ok]  '} {name} ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
    print("tpu_aot_compile:", "PASS" if not failed else f"{failed} FAILED")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
