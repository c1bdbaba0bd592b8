"""What the chips spend talking to each other: self seconds of the
device operations whose name marks a collective or a cross-chip
transfer, over the self seconds of all operations, every chip's added
up, in the traced sub-window. Signature verification is lane-parallel
with no term across lanes, so a sharded verify program that is local to
each chip reads 0 here; anything else is the compiler's partition, or a
placement, moving data between chips. Nothing to read with fewer than
two chips."""

import re

NAME = "cross_chip_op_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "crypto.tpu.mesh"
MOVES = "verdict_p50_ms"

# an operation as the trace names it ("%all-gather-start.3 = ...": the
# part before " = "), XLA's collectives and point-to-point transfers
CROSS_CHIP = re.compile(
    r"^%?(all-gather|all-reduce|all-to-all|reduce-scatter"
    r"|collective-permute|send|recv)(-start|-done)?([.\d]*)$"
)


def read(before: dict, after: dict, trace):
    if not trace or len(trace.get("chips") or {}) < 2:
        return None
    ops = trace.get("ops") or {}
    total = sum(ops.values())
    if total <= 0:
        return None
    cross = sum(
        secs for name, secs in ops.items()
        if CROSS_CHIP.match(name.split(" = ", 1)[0].strip())
    )
    return 100.0 * cross / total
