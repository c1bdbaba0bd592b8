"""What apply does not hide of the window's light check, a block applied:
the reactor's ``sync.verdict_wait`` seconds (blocked on a window
block's future; block i + 1's commit verifies while block i applies),
over the blocks applied in the window."""

from benchmark.lib import sync_books

NAME = "sync_verdict_wait_ms_per_block"
UNIT = "ms/block"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "blocksync.reactor"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return sync_books.ms_per_block(
        after,
        lambda s: s["sync.verdict_wait"],
    )
