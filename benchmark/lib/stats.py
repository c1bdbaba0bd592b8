"""Order statistics, one definition for every reader."""

from __future__ import annotations

from typing import Optional, Sequence


def percentile(sorted_vals: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks; None for no samples."""
    if not sorted_vals:
        return None
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)
