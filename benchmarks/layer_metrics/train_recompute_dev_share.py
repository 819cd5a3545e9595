"""Device time of the model's blocks run again over device busy time, mean
over chips: the ops under the remat wrapper's ``rematted_computation``, and the
ops under a ``jvp`` of the blocks the program also runs outside any jvp (a
manual VJP that replays a stage from its stashed input, as the 1F1B executor
does — not its head, which only runs inside its VJP). The ``phases`` note gives
the parts."""
from benchmarks import program_trace


def read(r):
    shares = program_trace.scope_shares(r)
    if not shares:
        return None
    return sum(
        100.0 * program_trace.recompute_seconds(d) / d["busy_s"] for d in shares["devices"]
    ) / len(shares["devices"])
