"""Device seconds of the verify programs in the traced sub-window
(profiler trace, every chip's added up), per lane the wire ledger saw
reach the device between the same two edges."""

from benchmark import opcount, trace_reduce

NAME = "kernel_us_per_lane"
UNIT = "us/lane"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "crypto.tpu.ed25519_batch"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    if not trace:
        return None
    return trace_reduce.program_us_per_lane(trace, opcount.PROGRAMS)
