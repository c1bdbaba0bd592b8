"""How late the open-loop producer enqueued a vote, 99th percentile: a
starved generator is not a fast plane."""

from benchmark.lib import stats

NAME = "gen_late_p99_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "load-generator"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    late = sorted(after["bench"]["late_s"])
    if len(late) < 1000:
        return None
    return stats.percentile(late, 0.99) * 1e3
