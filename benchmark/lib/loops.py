"""The closed loop the one-caller generators share."""

from __future__ import annotations

import time
from typing import Callable


def cpu_seconds() -> float:
    """CPU time of this process so far: user + system, every thread. On
    the chip's host this clock, like getrusage, moves in 10 ms ticks, so
    an 80 ms request reads 70 or 80 ms (PR 22)."""
    return time.process_time()


def closed_loop(plane, seconds: float, sigs: int,
                serve: Callable[[int], bool]) -> dict:
    """One request in flight, back to back, for ``seconds``. ``serve(i)``
    makes request ``i`` and says whether its verdict is the reference's.
    A request that raised, or during which a fallback counter moved, is
    recorded as such; each carries ``sigs`` signatures. ``cpu_units``
    holds, per served request, the CPU seconds the process spent while
    it was in flight. → the samples a generator's ``drive`` returns."""
    requests = []
    cpu_units = []
    t0 = time.monotonic()
    i = 0
    while time.monotonic() - t0 < seconds:
        marks = plane.fallbacks()
        cpu = cpu_seconds()
        t = time.monotonic()
        try:
            right = serve(i)
        except Exception as exc:  # noqa: BLE001 - a failed request, counted
            requests.append((time.monotonic() - t, sigs, "error"))
            plane.note(f"request {i} raised {exc!r}")
            i += 1
            continue
        latency = time.monotonic() - t
        cpu = cpu_seconds() - cpu
        i += 1
        if not right:
            status = "mismatch"
        elif plane.fallbacks() != marks:
            status = "fallback"
        else:
            status = "ok"
            cpu_units.append((cpu, sigs))
        requests.append((latency, sigs, status))
        plane.tick()
    return {
        "loop": "closed",
        "window_s": time.monotonic() - t0,
        "attempted": i,
        "requests": requests,
        "cpu_units": cpu_units,
        "extra_sigs": 0,
        "spans_s": {},
        "late_s": [],
    }
