"""The program's book by curve as the per-layer readers of a mixed
commit take it.

The wire ledger's ``curve_books()`` gives monotonic lanes by curve and
by where they ran (device / host) and each curve's pack seconds; the
``mixed_commit`` generator carries their difference over the window
under ``spans_s["curves"]``. A program without the book carries nothing,
and a reader then has nothing to read.
"""

from __future__ import annotations

from typing import Optional

from benchmark import trace_reduce
from benchmark.lib import books


def book(after: dict, curve: str) -> dict:
    """``curve``'s book over the window ({} where none was carried)."""
    curves = (after.get("bench", {}).get("spans_s") or {}).get("curves")
    return (curves or {}).get(curve) or {}


def device_share(after: dict, curve: str) -> Optional[float]:
    """``curve``'s share of the lanes that ran on the device in the
    window, or None where nothing was carried."""
    curves = (after.get("bench", {}).get("spans_s") or {}).get("curves")
    total = sum(float(b.get("device_lanes", 0))
                for b in (curves or {}).values())
    if total <= 0:
        return None
    return float(book(after, curve).get("device_lanes", 0)) / total


def program_us_per_lane(trace: Optional[dict], after: dict, pattern: str,
                        curve: str) -> Optional[float]:
    """Device microseconds of the programs matching ``pattern`` in the
    traced sub-window per ``curve`` lane between the trace's edges: the
    wire ledger's lanes there (``books.wire_lanes``) times the window's
    share of ``curve`` among the device lanes. None where there was no
    trace, no such program, or no book."""
    share = device_share(after, curve)
    if not trace or not share:
        return None
    edges = trace["counters"]
    lanes = books.wire_lanes(edges["before"], edges["after"]) * share
    secs = trace_reduce.program_seconds(trace, pattern)
    if lanes <= 0 or secs <= 0:
        return None
    return secs / lanes * 1e6
