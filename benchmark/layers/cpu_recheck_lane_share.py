"""Lanes the host pool verified although their flush took a device route
(the corruption audit's re-verifies, triage confirmations, hedges), as a
share of the lanes on device routes: what safety costs the host."""

from benchmark.lib import books

NAME = "cpu_recheck_lane_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.supervisor"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    on_device = books.device_lanes(before, after)
    if on_device <= 0:
        return None
    pool = books.delta(before, after, "cpu_pool_lanes")
    routed_host = books.delta(before, after, "decisions", "lanes", "cpu")
    return 100.0 * max(0.0, pool - routed_host) / on_device
