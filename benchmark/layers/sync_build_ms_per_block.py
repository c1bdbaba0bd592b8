"""What the reactor spends in front of a window's verdicts, a block applied:
its ``sync.build`` seconds (block ids, commit shape, quorum-prefix
selection, sign-bytes for the window) less the part sets built inside it
(``sync.part_set``: the store's, ``sync_store_ms_per_block``) plus
``sync.submit`` (the window's requests handed to the scheduler), over
the blocks applied in the window."""

from benchmark.lib import sync_books

NAME = "sync_build_ms_per_block"
UNIT = "ms/block"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "blocksync.reactor"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return sync_books.ms_per_block(
        after,
        lambda s: s["sync.build"] - s["sync.part_set"] + s["sync.submit"],
    )
