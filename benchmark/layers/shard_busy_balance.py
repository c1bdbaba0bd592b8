"""How evenly a sharded launch loads the chips: the least busy chip's
busy seconds over the busiest's, in the traced sub-window (profiler
trace, each chip's union of the intervals in which an operation ran).
The shards of a launch are equal and the mask's retire waits for the
slowest, so a chip that lags (a straggler, a chip that got the padding
or none of the lanes) shows here before it shows end to end. Nothing to
read with fewer than two chips."""

NAME = "shard_busy_balance"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "crypto.tpu.mesh"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    chips = (trace or {}).get("chips") or {}
    if len(chips) < 2:
        return None
    busy = [float(c["busy_s"]) for c in chips.values()]
    if max(busy) <= 0:
        return None
    return 100.0 * min(busy) / max(busy)
