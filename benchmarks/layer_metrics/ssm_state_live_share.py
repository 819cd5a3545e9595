"""Live lanes over the slots a decode step's pass moved, summed over the
traced decode dispatches (their records' ``state_lanes`` and
``state_slots_passed``): what a pass over every lane's slot costs an open loop
at partial occupancy. 100 % is a pass that moves live lanes' states only."""
from benchmarks import ssm_trace


def read(r):
    records = ssm_trace.decode_records(r) if r.get("kind") == "serving" else None
    if records is None:
        return None
    passed = sum(slots for _, _, slots in records)
    return 100.0 * sum(live for _, live, _ in records) / passed if passed else None
