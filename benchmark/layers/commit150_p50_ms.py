"""Median of the per-height 150-validator verify_commit on the consumer
thread (block validation), which the votes of that moment queue behind."""

from benchmark.lib import stats

NAME = "commit150_p50_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "types.validator_set"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    spans = sorted(after["bench"]["spans_s"].get("commit150", []))
    if not spans:
        return None
    return stats.percentile(spans, 0.5) * 1e3
