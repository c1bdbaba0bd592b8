"""How much of its window the reactor gets through in one pass: blocks
applied over ``sync_pass`` calls in the window, both from the reactor's
books. A window that stops at every validator-set change reads about 1
on a chain whose set moves at every height; one that carries its lanes
past the change reads towards ``verify_window`` (16)."""

NAME = "sync_window_blocks_per_pass"
UNIT = "blocks/pass"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "blocksync.reactor"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    sync = (after.get("bench", {}).get("spans_s") or {}).get("sync") or {}
    passes = sync.get("passes", 0)
    if passes <= 0 or "blocks_applied" not in sync:
        return None
    return sync["blocks_applied"] / passes
