"""Mean wait of a request from submit to the start of its dispatch:
the scheduler's request_wait_seconds, sum over count, over the window."""

from benchmark.lib import books

NAME = "queue_wait_mean_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.scheduler"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    n = books.delta(before, after, "sched", "wait_count")
    if n <= 0:
        return None
    return books.delta(before, after, "sched", "wait_sum_s") / n * 1e3
