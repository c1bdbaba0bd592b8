"""Cross-client coalescing as the daemon's scheduler did it: the requests
(one a REQ frame: a client's round trip) its flushes carried, over the
flushes, over the window. The fleet has 32 requests in flight at most;
what one flush gathers of them says how far the deadline and the
clients' pace let the device work on many clients' lanes at once."""

NAME = "fleet_requests_per_flush"
UNIT = "req/flush"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "crypto.scheduler"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    fleet = (after.get("bench", {}).get("spans_s") or {}).get("fleet")
    if not fleet:
        return None
    flushes = fleet.get("sched_dispatches", 0)
    if flushes <= 0:
        return None
    return fleet.get("sched_requests", 0) / flushes
