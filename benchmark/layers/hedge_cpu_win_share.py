"""Device dispatches whose hedge fired (the dispatch overran twice its
predicted 99th percentile) and whose verdict the host pool delivered
first, as a share of the device dispatches in the window. Such a request
got a correct verdict, late and on the host's cores: it is served, not
failed, and this is where it shows. In the runs that had just compiled
their executables 3 of 244 dispatches ended so, in 20 warm runs none
(chip runs, PR 22)."""

from benchmark.lib import books

NAME = "hedge_cpu_win_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.supervisor"
MOVES = "verified_sigs_per_s"


def read(before: dict, after: dict, trace):
    dispatches = books.delta(before, after, "supervisor", "device_dispatches")
    if dispatches <= 0:
        return None
    wins = books.delta(before, after, "supervisor", "hedge_wins_cpu")
    return 100.0 * wins / dispatches
