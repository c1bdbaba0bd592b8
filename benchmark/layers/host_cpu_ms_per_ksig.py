"""What the verify plane costs the host that also runs consensus:
process CPU time (user + system, every thread) per thousand signatures
verified. On a machine whose host cores tie its chip, this tells "the
TPU did it" from "the host pool did it": a route change that wins
latency by burning the host shows here.

Taken per unit of work the generator marks (one request of a closed
loop, one height of the open loop) and reported as the median over the
units, so that the figure is what a request typically costs. The
window's total swings with how many flushes the supervisor's unseeded
5 % audit happens to sample (8 to 15 of 270 in runs of one code, each
re-verifying 6,464 lanes on every host core: a spread of 8-13 %, PR 22);
`cpu_recheck_lane_share` carries that part, exactly.

A per-layer metric and not an end-to-end one: the host's CPU clock
moves in 10 ms ticks, so a unit reads in steps (1.547 ms/ksig for an
80 ms blocksync request, 14 % of it), and the driver's check of PR 22
read spreads of 14 % there and of 4 % and 45 % in the two sets of the
open loop, which no bound up to the cap of 0.25 admits (PERF.md,
section 6). Whatever layer burns the host moves it."""

import statistics

NAME = "host_cpu_ms_per_ksig"
UNIT = "ms/ksig"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "host-process"
MOVES = "verdict_p50_ms"
MIN_UNITS = 10


def read(before: dict, after: dict, trace):
    units = [(cpu, sigs) for cpu, sigs in after["bench"]["cpu_units"]
             if sigs > 0]
    if len(units) < MIN_UNITS:
        return None
    return statistics.median(cpu * 1e3 / (sigs / 1e3) for cpu, sigs in units)
