"""Signatures whose verdict reached the caller in the window, over the
window's seconds. For closed-loop cells: in an open loop this is the
offered rate, and the reader returns nothing."""

NAME = "verified_sigs_per_s"
UNIT = "sigs/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run: dict):
    if run["loop"] != "closed" or run["window_s"] <= 0 or not run["ok"]:
        return None
    return run["sigs_verified"] / run["window_s"]
