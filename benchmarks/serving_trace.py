"""Telling the serving programs apart in a device trace.

``PagedServingEngine._register_program`` wraps every program as
``jax.jit(fn)``, so ``pctx``, ``psfx`` and ``pdecode`` all run as module
``jit_fn`` and only their fingerprints differ. Until the program names them,
the traced run turns on the engine's flight recorder (``EngineTracer``) and
pairs its dispatch records with the device's executions *in order*: one
device, one stream, so the k-th large ``jit_fn`` execution is the k-th model
dispatch the engine recorded while the profiler ran. The counts must match
exactly and every fingerprint must land on one kind of program; otherwise
nothing is returned and the reason is given — never a guess.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmarks import xplane

# the engine's small programs (lane_set, table_delta, copy_block) run for
# microseconds; a model forward at published widths for milliseconds
SMALL_PROGRAM_S = 0.5e-3
DISPATCH_EVENTS = ("dispatch", "prefill", "prefill_chunk")


def engine_dispatches(engine_steps: List[dict]) -> List[Tuple[str, dict]]:
    """(kind, args) of every model dispatch in the flight recorder's steps,
    in order: ``decode`` or ``prefill``."""
    events = []
    for step in engine_steps:
        for ph, name, t0, _t1, args in step["events"]:
            if ph == "X" and name in DISPATCH_EVENTS:
                events.append((t0, "decode" if name == "dispatch" else "prefill", args))
    events.sort(key=lambda e: e[0])
    return [(kind, args) for _, kind, args in events]


def classify(result: Dict[str, Any]) -> Tuple[Optional[Dict[str, Any]], str]:
    """({"decode": [(seconds, lanes)], "prefill": [(seconds, tokens)]}, "") or
    (None, why not); worked out once per result."""
    if "serving_programs" not in result:
        result["serving_programs"] = _classify(result)
    return result["serving_programs"]


def _classify(result: Dict[str, Any]) -> Tuple[Optional[Dict[str, Any]], str]:
    prof, reduced = result.get("profile"), result.get("reduced")
    if not prof or not reduced or not reduced.get("module_runs"):
        return None, "no device trace"
    big_ids = set()
    for name, _start, dur in reduced["module_runs"]:
        if xplane.module_key(name)[0] == "jit_fn" and dur >= SMALL_PROGRAM_S:
            big_ids.add(name)
    runs = [(n, d) for n, _s, d in reduced["module_runs"] if n in big_ids]
    dispatches = engine_dispatches(prof.get("engine_steps", []))
    if len(runs) != len(dispatches):
        return None, (
            f"{len(runs)} large jit_fn executions on the device, "
            f"{len(dispatches)} model dispatches recorded by the engine"
        )
    kind_of: Dict[str, str] = {}
    decode, prefill = [], []
    for (name, dur), (kind, args) in zip(runs, dispatches):
        if kind_of.setdefault(name, kind) != kind:
            return None, f"program {name} pairs with both decode and prefill dispatches"
        if kind == "decode":
            decode.append((dur, int(args.get("lanes", 0))))
        else:
            prefill.append((dur, int(args.get("tokens", 0))))
    return {"decode": decode, "prefill": prefill}, ""
