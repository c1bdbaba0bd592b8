"""The blocksync reactor's books as the per-layer readers take them.

``BlocksyncReactor.sync_counters()`` gives monotonic seconds by
``sync.*`` / ``exec.*`` stage and the blocks applied; the generator adds
them up over the window's epochs and carries the sums under
``spans_s["sync"]``. A program without the counters carries nothing, and
a reader then has nothing to read.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional


def ms_per_block(after: dict,
                 seconds_of: Callable[[Dict[str, float]], float]
                 ) -> Optional[float]:
    """``seconds_of(seconds by stage)`` over the blocks applied in the
    window, in ms; None where the run carries no books, applied nothing
    or lacks a stage the reader names."""
    sync = (after.get("bench", {}).get("spans_s") or {}).get("sync") or {}
    blocks = sync.get("blocks_applied", 0)
    seconds = sync.get("seconds")
    if blocks <= 0 or not seconds:
        return None
    try:
        return seconds_of(seconds) / blocks * 1e3
    except KeyError:
        return None
