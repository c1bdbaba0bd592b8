"""Registration churn: REGISTER frames the daemon received over REQ frames,
over the window. A fleet whose validator sets fit the key store
registers once a connection, before the window, and reads 0; a store
that evicts them, or whose freshness rule stales clients that nothing
happened to, makes a client register again inside its request."""

NAME = "register_per_request"
UNIT = "frames/req"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.tpu.keystore"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    fleet = (after.get("bench", {}).get("spans_s") or {}).get("fleet")
    if not fleet:
        return None
    reqs = fleet.get("req_frames", 0)
    if reqs <= 0:
        return None
    return fleet.get("register_frames", 0) / reqs
