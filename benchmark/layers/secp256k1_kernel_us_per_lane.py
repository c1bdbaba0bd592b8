"""Device seconds of the secp256k1 verify program (named
``jit__secp256k1_verify_core`` in the trace) in the traced sub-window,
per secp256k1 lane between the same two edges: the wire ledger's lanes
there times the window's secp256k1 share of the device lanes (the
program's book by curve, carried by the ``mixed_commit`` generator)."""

from benchmark import secp_opcount
from benchmark.lib import curve_books

NAME = "secp256k1_kernel_us_per_lane"
UNIT = "us/lane"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "crypto.tpu.secp256k1_batch"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    return curve_books.program_us_per_lane(
        trace, after, secp_opcount.PROGRAMS, "secp256k1"
    )
