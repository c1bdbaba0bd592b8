"""Coalescing: signatures submitted to the scheduler over the dispatches
it made, over the window."""

from benchmark.lib import books

NAME = "lanes_per_flush"
UNIT = "lanes"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "crypto.scheduler"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    n = books.delta(before, after, "sched", "dispatches")
    if n <= 0:
        return None
    return books.delta(before, after, "sched", "signatures") / n
