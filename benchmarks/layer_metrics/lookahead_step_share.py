"""Of the decode dispatches the engine's flight recorder saw while the profiler
ran, the share the step loop dispatched ahead of the device: the ``dispatch``
record's ``mode`` is ``async`` for a program sent before the one in flight was
read back, ``sync`` for the decode of a drained step (a scheduler event — an
admission, a prefill chunk, a finish the host could count, a dry pool — or a
program that does not run ahead at all, which reads 0)."""
from benchmarks import serving_trace


def read(r):
    steps = (r.get("profile") or {}).get("engine_steps")
    if not steps:
        return None     # no flight recorder in this run: nothing to read
    modes = [
        args.get("mode")
        for kind, args in serving_trace.engine_dispatches(steps)
        if kind == "decode"
    ]
    if not modes:
        r.setdefault("notes", []).append(
            "lookahead_step_share: no decode dispatch in the traced segment, read as 0"
        )
        return 0.0
    return 100.0 * sum(m == "async" for m in modes) / len(modes)
