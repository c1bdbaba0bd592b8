"""The verify kernel's share of its roofline: the least time one lane
can take (opcount.py over peaks.json: the larger of int8-equivalent
operations over the MXU's integer peak and bytes over HBM bandwidth;
the compute bound holds) over the kernel time per lane. The kernel is
32-bit integer work on the vector unit, for which no peak is published,
so the share reads small and says how far the formulation is from the
matrix unit."""

import json
import os

from benchmark import opcount, trace_reduce

NAME = "ed25519_verify_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "crypto.tpu.ed25519_batch"
MOVES = "verified_sigs_per_s"

_PEAKS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json"
)


def read(before: dict, after: dict, trace):
    us = trace and trace_reduce.program_us_per_lane(trace, opcount.PROGRAMS)
    if not us:
        return None
    with open(_PEAKS) as fh:
        peaks = json.load(fh)
    kind = after["bench"]["device_kind"]
    if kind not in peaks:
        raise KeyError(f"peaks.json has no entry for device_kind {kind!r}")
    least = opcount.least_seconds_per_lane(peaks[kind])
    return 100.0 * least["seconds"] * 1e6 / us
