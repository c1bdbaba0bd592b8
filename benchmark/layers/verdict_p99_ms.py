"""The tail of the vote path's latency, made steady against a shared
host: the 99th percentile within each consecutive block of 300 served
requests, in the order they were served, and the median over the blocks.
One stall of the machine spoils one block and not the run's figure; the
samples beyond the percentile still number three a block, thirty over
the ten blocks asked for. (The 99th percentile of the whole window swung
from 12 to 943 ms between runs of one code on the chip's host, PR 22.)

A per-layer metric and not an end-to-end one: the driver's check of
PR 22 read spreads of 6.4 % and 13.3 % in its two sets of six runs,
which no bound up to the cap of 0.25 admits (PERF.md, section 6). It
is what the consumer's discipline makes of the load: votes queue behind
whatever the one thread is doing, the height's verify_commit first."""

import statistics

from benchmark.lib import stats

NAME = "verdict_p99_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "consensus.state"
MOVES = "verdict_p50_ms"
BLOCK = 300
MIN_BLOCKS = 10


def read(before: dict, after: dict, trace):
    lat = after["bench"]["latency_ms_in_order"]
    blocks = [
        sorted(lat[i:i + BLOCK])
        for i in range(0, len(lat) - BLOCK + 1, BLOCK)
    ]
    if len(blocks) < MIN_BLOCKS:
        return None
    return statistics.median(stats.percentile(b, 0.99) for b in blocks)
