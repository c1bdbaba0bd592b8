"""What one round trip costs inside the daemon: the service's seconds from
a REQ frame decoded to its response handed to the connection's writer
(admission, queue wait, the flush it rode, verdict encode), over the
responses, over the window. A mean: the service keeps one total."""

NAME = "service_server_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.service"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    fleet = (after.get("bench", {}).get("spans_s") or {}).get("fleet")
    if not fleet:
        return None
    served = fleet.get("served", 0)
    if served <= 0 or "served_s" not in fleet:
        return None
    return fleet["served_s"] / served * 1e3
