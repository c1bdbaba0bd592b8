"""What a validator-set change costs the executor, a block applied: its
``exec.valset_update`` seconds (the change set applied to the next
validators, and the state store's write of the whole changed set), over
the blocks applied in the window. A program without the stage has
nothing to read."""

from benchmark.lib import sync_books

NAME = "sync_valset_update_ms_per_block"
UNIT = "ms/block"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "state.execution"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return sync_books.ms_per_block(
        after,
        lambda s: s["exec.valset_update"],
    )
