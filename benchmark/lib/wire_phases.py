"""A flush's life by phase, per lane: readers of the wire ledger's flush
phases (``cometbft_tpu/crypto/wire.FlushRecord``, PR 34).

``books.Books.wire()`` passes every ``(route, phase)`` series of
``verify_wire_phase_seconds`` through as ``wire.phase_s[route][phase]``,
so the phases the program books when a flush record closes (``queue``,
``assemble``, ``route``, ``lead``, ``columns``, ``build_exposed``,
``tail``) and a launch's ``fetch`` are in every snapshot. Each reader
here sums their difference over the window on every route but ``cpu``
(a flush the floor kept on the host reached no lane to divide by) and
divides by ``books.wire_lanes``: the unit of ``pack_us_per_lane`` and
``device_leg_us_per_lane``, so one column of us/lane adds up to a
request. A program that books none of a reader's phases (every commit
before PR 34) gives it nothing to read: None, and nothing raises.

One ``Reader`` a metric, with the attributes of a ``layers/<name>.py``
module (NAME, UNIT, BETTER, SOURCE, LAYER, MOVES, ``read``) and the
cells it has something to read in (CELLS). ``run_phases.py`` puts them
in a run's line; ``layers/`` files and ``BENCHMARK.json`` entries made
from this table are a ``benchmark`` PR's (PERF.md section 7).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from benchmark.lib import books

HOST_ROUTE = "cpu"

_SCHEDULED = ("qa150-blocksync", "qa150-blocksync-apply", "light150-fleet")
_COMMITS = ("mega10k-commit", "mega10k-commit-x4")


def phase_s(before: dict, after: dict, *phases: str) -> Optional[float]:
    """Seconds booked to ``phases`` in between on every route but the
    host's; None where no such route has any of them on its books."""
    total, found = 0.0, False
    series = after["wire"]["phase_s"]
    for route, per in series.items():
        if route == HOST_ROUTE or not any(ph in per for ph in phases):
            continue
        found = True
        moved = books.delta_map(before, after, "wire", "phase_s", route)
        total += sum(moved.get(ph, 0.0) for ph in phases)
    return total if found else None


class Reader:
    """``phases`` seconds per lane that reached the device, in us."""

    UNIT = "us/lane"
    BETTER = "lower"
    SOURCE = "program_counter"
    MOVES = "verdict_p50_ms"

    def __init__(self, name: str, layer: str, phases: Tuple[str, ...],
                 cells: Tuple[str, ...]):
        self.NAME = name
        self.LAYER = layer
        self.PHASES = phases
        self.CELLS = cells

    def read(self, before: dict, after: dict, trace) -> Optional[float]:
        lanes = books.wire_lanes(before, after)
        seconds = phase_s(before, after, *self.PHASES)
        if lanes <= 0 or seconds is None:
            return None
        return seconds / lanes * 1e6


class ShareReader(Reader):
    """``phases`` seconds as a share of ``of`` seconds, in %."""

    UNIT = "%"

    def __init__(self, name, layer, phases, of, cells):
        super().__init__(name, layer, phases, cells)
        self.OF = of

    def read(self, before: dict, after: dict, trace) -> Optional[float]:
        part = phase_s(before, after, *self.PHASES)
        whole = phase_s(before, after, *self.OF)
        if part is None or not whole or whole <= 0:
            return None
        return 100.0 * part / whole


READERS: Dict[str, Reader] = {r.NAME: r for r in (
    Reader("queue_us_per_lane", "crypto.scheduler", ("queue",), _SCHEDULED),
    Reader("flush_host_us_per_lane", "crypto.scheduler",
           ("assemble", "route", "demux"), _SCHEDULED),
    Reader("lead_us_per_lane", "crypto.supervisor", ("lead",),
           _SCHEDULED + _COMMITS),
    Reader("columns_us_per_lane", "crypto.batch", ("columns",),
           _SCHEDULED[:2]),
    Reader("fetch_us_per_lane", "types.validator_set", ("fetch",), _COMMITS),
    ShareReader("build_exposed_share", "crypto.tpu.mesh",
                ("build_exposed",), ("fetch", "pack"),
                ("qa150-blocksync",) + _COMMITS + ("light150-fleet",)),
    Reader("tail_us_per_lane", "crypto.supervisor", ("tail",),
           _SCHEDULED + _COMMITS),
)}


def names_for(cell: str):
    """The readers that have something to read in ``cell``, in order."""
    return [name for name, r in READERS.items() if cell in r.CELLS]
