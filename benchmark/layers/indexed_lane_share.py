"""How much of the fleet's traffic the registration handshake carried:
lanes the daemon received as 100 B indexed rows over all lanes it
received, over the window. A client whose registration went stale sends
128 B compact rows (and packs its keys again) until it has resynced."""

NAME = "indexed_lane_share"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "crypto.tpu.keystore"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    fleet = (after.get("bench", {}).get("spans_s") or {}).get("fleet")
    if not fleet:
        return None
    lanes = fleet.get("lanes_indexed", 0) + fleet.get("lanes_compact", 0)
    if lanes <= 0:
        return None
    return 100.0 * fleet.get("lanes_indexed", 0) / lanes
