"""Of the lanes the apply-time quorum walks of speculated blocks needed
(the reactor's ``tally_lanes``), the share no speculated lane covered
under the true set's key and the walk verified itself, on the sync
thread, under the routing floor (``speculation_miss_lanes``): a seat
that joined inside the window, a speculation that stopped short. A
program that speculates nothing has neither counter, and nothing to
read."""

NAME = "sync_speculation_miss_lane_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "blocksync.reactor"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    sync = (after.get("bench", {}).get("spans_s") or {}).get("sync") or {}
    walked = sync.get("tally_lanes", 0)
    if walked <= 0 or "speculation_miss_lanes" not in sync:
        return None
    return 100.0 * sync["speculation_miss_lanes"] / walked
