"""The host's part of one verify_commit: sign-bytes, lane build, hash
and pack, mask check. The benchmark's span around the call, less the
seconds the wire ledger booked to the device leg (compute + d2h) of the
window's dispatches, per commit; a mean over the window."""

from benchmark.lib import books

NAME = "commit_host_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "types.validator_set"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    spans = after["bench"]["spans_s"].get("verify_commit")
    if not spans:
        return None
    device_leg = books.wire_phase_s(before, after, "compute", "d2h")
    return (sum(spans) - device_leg) / len(spans) * 1e3
