"""What the network boundary costs one round trip: the clients' seconds
from a REQ frame handed to the socket to its RESP decoded on the
receiver thread, less the daemon's seconds for the same responses
(service_server_ms): both socket legs, the server's read loop and
writer, the client's receiver hand-off. Means over the window; the few
round trips in flight at an edge are in one total and not the other."""

NAME = "service_socket_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.service"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    fleet = (after.get("bench", {}).get("spans_s") or {}).get("fleet")
    if not fleet:
        return None
    served, rtts = fleet.get("served", 0), fleet.get("client_rtts", 0)
    if served <= 0 or rtts <= 0 or "client_rtt_s" not in fleet:
        return None
    return (fleet["client_rtt_s"] / rtts - fleet["served_s"] / served) * 1e3
