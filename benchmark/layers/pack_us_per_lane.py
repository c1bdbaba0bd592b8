"""Host seconds the dispatch loops spent materialising chunks (hash,
transpose, pad), per lane that reached the device: the wire ledger's
pack phase over its lanes, over the window."""

from benchmark.lib import books

NAME = "pack_us_per_lane"
UNIT = "us/lane"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.tpu.mesh"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    lanes = books.wire_lanes(before, after)
    if lanes <= 0:
        return None
    return books.wire_phase_s(before, after, "pack") / lanes * 1e6
