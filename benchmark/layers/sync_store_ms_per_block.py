"""What the block store's side of a synced block costs, a block applied:
the part set made of the block (``sync.part_set``: the block encoded,
cut into 64 KB parts, their Merkle proofs) plus ``sync.save_block``
(parts, meta, commit and seen commit written)."""

from benchmark.lib import sync_books

NAME = "sync_store_ms_per_block"
UNIT = "ms/block"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "store"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return sync_books.ms_per_block(
        after,
        lambda s: s["sync.part_set"] + s["sync.save_block"],
    )
