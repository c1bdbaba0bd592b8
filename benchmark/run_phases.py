#!/usr/bin/env python3
"""One run of one cell with a flush's life by phase in its line.

    python3 benchmark/run_phases.py --workload <cell> --seed <n> --seconds <s> [--trace 1]

``run.py`` as it is, with no profiler session unless ``--trace 1`` asks
for one (the flush phases are program counters: they need none, and a
session slows the host): the line's ``metrics`` hold the cell's
end-to-end metrics, its per-layer metrics (those fed by the device trace
fall silent without a session) and the flush-phase metrics of
``lib/wire_phases.py`` that have something to read in the cell; and
``flush_life`` holds the mean of the wire ledger's last flush records
(``/debug/verify``'s ``wire.flushes``; the records of the ``cpu`` route
apart), the partition's sum beside the records' own life.  The driver
does not run this file: it is the builder's way to these numbers until
a ``benchmark`` PR lists the readers in the cells' ``layers`` (PERF.md
section 7).
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402
from benchmark.lib import wire_phases  # noqa: E402

PARTITION = ("queue", "assemble", "route", "lead", "stream", "tail", "demux")


class Session(run.SubWindowTrace):
    """run.py's traced sub-window, which also keeps the wire ledger's
    flush records as they stand when the window ends (the harness stops
    its tracer right behind the last request)."""

    flushes: list = []

    def stop(self) -> None:
        # a program from before PR 34 has a ledger and no flush records
        flushes = getattr(
            getattr(self.plane.node, "wire_ledger", None), "flushes", None
        )
        if flushes is not None:
            Session.flushes = flushes()
        super().stop()


class NoSession(Session):
    """The same with no profiler behind it: never armed, so no tick
    starts a session and ``stop`` has none to stop."""

    def arm(self) -> None:
        pass

    def reduce(self):
        return None


class _EndToEnd:
    """An end-to-end reader behind a per-layer reader's signature."""

    def __init__(self, mod):
        self.NAME, self.UNIT, self._read = mod.NAME, mod.UNIT, mod.read

    def read(self, before: dict, after: dict, trace):
        return self._read(after["bench"])


def flush_life(flushes: list) -> dict:
    """{route kind: {records, lanes, life_ms, verify_ms, sum_ms (the
    partition's), phases_ms}}: means over the ledger's last records, the
    host's flushes apart from those that reached the device."""
    out = {}
    for kind, rows in (
        ("device", [f for f in flushes if f["route"] != "cpu"]),
        ("host", [f for f in flushes if f["route"] == "cpu"]),
    ):
        if not rows:
            continue
        n = len(rows)
        phases = sorted({ph for f in rows for ph in f["phases_ms"]})
        mean = {
            ph: sum(f["phases_ms"].get(ph, 0.0) for f in rows) / n
            for ph in phases
        }
        out[kind] = {
            "records": n,
            "routes": sorted({f["route"] for f in rows}),
            "lanes": sum(f["lanes"] for f in rows) / n,
            "launches": sum(f["launches"] for f in rows) / n,
            "life_ms": sum(f["life_ms"] for f in rows) / n,
            "verify_ms": sum(f["verify_ms"] for f in rows) / n,
            "sum_ms": sum(mean.get(ph, 0.0) for ph in PARTITION),
            "phases_ms": mean,
        }
    return out


def phases_cell(cell: dict, seed: int, seconds: float, trace: bool,
                device: dict, expect_platform: str = "tpu") -> dict:
    """``run.run_cell`` with the end-to-end and the flush-phase readers
    among the cell's layers, and the ledger's flush records in the line.
    Nothing of ``run`` stays changed behind it."""
    e2e = list(cell["cell"]["end_to_end"])
    extra = wire_phases.names_for(cell["name"])
    cell = dict(cell, cell=dict(
        cell["cell"],
        layers=e2e + list(cell["cell"]["layers"]) + extra,
    ))
    load, tracer = run.load_module, run.SubWindowTrace

    def load_module(kind: str, name: str):
        if kind == "layers" and name in e2e:
            return _EndToEnd(load("end_to_end", name))
        if kind == "layers" and name in extra:
            return wire_phases.READERS[name]
        return load(kind, name)

    run.load_module = load_module
    run.SubWindowTrace = Session if trace else NoSession
    Session.flushes = []
    try:
        line = run.run_cell(cell, seed, seconds, True, device,
                            expect_platform)
    finally:
        run.load_module, run.SubWindowTrace = load, tracer
    line["flush_life"] = flush_life(Session.flushes)
    return line


def main(argv=None) -> int:
    ap = run.argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = run.resolve_cell(args.workload)
    device = run.gate(cell["chips"])
    if device is None:
        return run.NO_TPU_EXIT
    line = phases_cell(cell, args.seed, args.seconds, bool(args.trace),
                       device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    # the hard exit of run.py, for the same reason
    try:
        rc = main()
    except SystemExit as exc:
        rc = exc.code if isinstance(exc.code, int) else 1
    except BaseException:  # noqa: BLE001 - report, then exit non-zero
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
