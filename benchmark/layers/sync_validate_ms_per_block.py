"""Both full commit checks of a synced block, a block applied: the
reactor's ``sync.validate`` (``validate_block`` before the block is
saved) plus the executor's ``exec.validate`` (``validate_block`` again
inside ``apply_block``): twice a ``verify_commit`` of every signature
of the block's LastCommit, under the routing floor, so on the host."""

from benchmark.lib import sync_books

NAME = "sync_validate_ms_per_block"
UNIT = "ms/block"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "state.validation"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return sync_books.ms_per_block(
        after,
        lambda s: s["sync.validate"] + s["exec.validate"],
    )
