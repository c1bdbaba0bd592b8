"""The secp256k1 verify kernel's share of its roofline: the least time
one lane can take (secp_opcount.py over peaks.json: the larger of
int8-equivalent operations over the MXU's integer peak and bytes over
HBM bandwidth; the compute bound holds) over the program's device time
per secp256k1 lane (secp256k1_kernel_us_per_lane). The kernel is 32-bit
integer work on the vector unit, for which no peak is published, so the
share reads small, as ed25519_verify_roofline does."""

import json
import os

from benchmark import secp_opcount
from benchmark.lib import curve_books

NAME = "secp256k1_verify_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "crypto.tpu.secp256k1_batch"
MOVES = "verified_sigs_per_s"

_PEAKS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json"
)


def read(before: dict, after: dict, trace):
    us = curve_books.program_us_per_lane(
        trace, after, secp_opcount.PROGRAMS, "secp256k1"
    )
    if not us:
        return None
    with open(_PEAKS) as fh:
        peaks = json.load(fh)
    kind = after["bench"]["device_kind"]
    if kind not in peaks:
        raise KeyError(f"peaks.json has no entry for device_kind {kind!r}")
    least = secp_opcount.least_seconds_per_lane(peaks[kind])
    return 100.0 * least["seconds"] * 1e6 / us
