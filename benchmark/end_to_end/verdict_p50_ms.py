"""Median time from when a request was due (open loop) or issued
(closed loop) to its verdict in the caller's hands. Failed requests have
no latency."""

from benchmark.lib import stats

NAME = "verdict_p50_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run: dict):
    lat = run["latency_ms"]
    if not lat:
        return None
    return stats.percentile(lat, 0.5)
