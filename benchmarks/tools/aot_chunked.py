"""``aot.py`` for a serving cell with one kind of cache whose top rung is long
and whose prefill is chunked: the same programs at real size for a described
v5e, no chip attached, with the prefill ladder stopping at the chunk — a
chunked engine dispatches nothing longer (PR 41), and ``aot.py`` as shipped
completes the ladder to a whole-prompt ``pctx[max_seq_len]`` that dies on a
16k rung.

    python3 benchmarks/tools/aot_chunked.py <workload> [pool_blocks] [--hlo-hash]

``--hlo-hash`` prints a hash of each program's optimized HLO with what differs
between two checkouts of the same program stripped (metadata, stack frames, the
location tables), to compare a parent with a change; it works on any serving
cell ``aot.py`` compiles."""

import hashlib
import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location("aot", os.path.join(HERE, "aot.py"))
aot = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(aot)       # sets the environment for a described v5e

from jax.experimental import topologies  # noqa: E402

from benchmarks import spec  # noqa: E402


def stripped(text: str) -> str:
    """Optimized HLO without source locations: ``metadata={...}``, stack frame
    ids, the ``FileNames`` ... ``StackFrames`` header tables and their
    ``n {...}`` rows (absolute paths and line numbers live there)."""
    text = re.sub(r"metadata=\{[^}]*\}", "", text)
    text = re.sub(r",? ?stack_frame_id=\d+", "", text)
    keep, skipping = [], False
    for line in text.splitlines():
        if re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\b", line):
            skipping = True
            continue
        if skipping and (re.match(r"^\d+ [\"{]", line) or not line.strip()):
            continue
        skipping = False
        keep.append(line)
    return "\n".join(keep)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    cell = spec.load_cell(args[0])
    family = spec.load_family(cell.config["family"])
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    print(f"compiling {cell.name} for {devices[0].device_kind} (described, not attached)")
    sizes = cell.traffic["engine"]
    chunk = int(sizes["prefill_chunk_tokens"])
    from neuronx_distributed_llama3_2_tpu.serving import catalog

    complete = catalog.complete_ladder

    def chunked(ladder, top):
        if chunk and tuple(ladder) == tuple(sizes["prefill_buckets"]):
            return [b for b in ladder if b <= chunk]
        return complete(ladder, top)

    catalog.complete_ladder = chunked
    if "--hlo-hash" in sys.argv:
        report = aot.report

        def hashed(name, compiled):
            report(name, compiled)
            print(f"  hlo {hashlib.sha256(stripped(compiled.as_text()).encode()).hexdigest()[:16]}",
                  flush=True)

        aot.report = hashed
    aot.serving(cell, family, devices,
                int(args[1]) if len(args) > 1 else int(sizes["pool_blocks"]))


if __name__ == "__main__":
    main()
