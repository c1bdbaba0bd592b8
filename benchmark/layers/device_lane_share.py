"""Of the lanes callers handed the plane in the window, the share that
took a device route (decision ledger: single, sharded, indexed; wire
ledger: the resident commit path). The rest the router sent to the host
pool."""

from benchmark.lib import books

NAME = "device_lane_share"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "crypto.scheduler"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    on_device = books.device_lanes(before, after)
    routed = books.delta_map(before, after, "decisions", "lanes")
    host = sum(v for r, v in routed.items() if r not in books.DEVICE_ROUTES)
    if on_device + host <= 0:
        return None
    return 100.0 * on_device / (on_device + host)
