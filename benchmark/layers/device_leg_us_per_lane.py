"""The host's view of device residency: the wire ledger's compute + d2h
seconds (issue and retire wait, which split differently by backend and
whose sum is the time a chunk was the device's), per lane that reached
the device. Not kernel time: transfer and launch latency are in it."""

from benchmark.lib import books

NAME = "device_leg_us_per_lane"
UNIT = "us/lane"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.tpu.mesh"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    lanes = books.wire_lanes(before, after)
    if lanes <= 0:
        return None
    return books.wire_phase_s(before, after, "compute", "d2h") / lanes * 1e6
