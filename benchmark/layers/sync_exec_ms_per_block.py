"""What applying a block costs beyond its commit check, a block applied:
the reactor's ``sync.apply`` seconds less the executor's
``exec.validate``: BeginBlock, the DeliverTx calls, EndBlock, the ABCI
responses saved, the state transition, the app's Commit, the mempool
update, the state saved, the events fired."""

from benchmark.lib import sync_books

NAME = "sync_exec_ms_per_block"
UNIT = "ms/block"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "state.execution"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    return sync_books.ms_per_block(
        after,
        lambda s: s["sync.apply"] - s["exec.validate"],
    )
