"""From a profiler trace (.xplane.pb) to numbers.

One reduction for every PR: device busy intervals per chip, device time
per operation and per program, and the idle gaps attributed to what the
host was doing, read from the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names that start with ``bench:``).

Layout the reduction expects, as the TPU profiler writes it and as
``tests/benchmark/test_trace_reduce.py`` pins it on a small trace:

* a device is a plane named ``/device:TPU:<n>`` (nothing after the
  number; ``/device:TPU:0 SparseCore`` and the like are not chips);
* on it, line ``XLA Ops`` holds one event per operation that ran, and
  line ``XLA Modules`` one event per program (``jit_<name>(<id>)``);
* host threads are lines of plane ``/host:CPU``; a ``TraceAnnotation``
  is an event on the line of the thread that opened it;
* ``start_ns``/``duration_ns`` of every plane are on one clock.

No jax import at module level and no device call anywhere: the tests
run this on the CPU platform.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
UNATTRIBUTED = "(no benchmark span)"

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def load(path: str) -> List[dict]:
    """The trace as plain data: [{"name", "lines": [{"name", "events":
    [(name, start_s, dur_s)]}]}]. ``path`` is an ``.xplane.pb``, or a
    ``.pbtxt`` holding the same XSpace as text (the tests' small trace)."""
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt"):
        with open(path) as fh:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(fh.read())
            )
    else:
        data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({
                "name": line.name,
                "events": [
                    (ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9)
                    for ev in line.events
                ],
            })
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (sorted, disjoint) leaves."""
    out = []
    at = lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: Sequence[tuple]) -> List[Tuple[str, float, float]]:
    """(name, start_s, self_s) per event of ONE line: an event's duration
    less that of the events nested inside it (a ``while`` holds its
    body's operations on the same line), so that sums do not count a
    second twice."""
    out: List[list] = []
    stack: List[Tuple[float, int]] = []  # (end, index into out)
    for name, start, dur in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= dur
        out.append([name, start, dur])
        stack.append((start + dur, len(out) - 1))
    return [(n, s, max(0.0, d)) for n, s, d in out]


def _line(plane: dict, name: str) -> List[tuple]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(planes: Sequence[dict]) -> List[Tuple[str, float, float]]:
    """(name, start_s, end_s) of every benchmark span, by start."""
    out = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda t: t[1])


def attribute(gap_list: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle seconds by the benchmark span that covers them. Where spans
    nest or overlap, the one opened last (the innermost) takes the time;
    time no span covers goes to UNATTRIBUTED. One sweep over the spans'
    boundaries, then one pass over the gaps."""
    out: Dict[str, float] = {}
    gap_list = sorted(gap_list)
    if not gap_list:
        return out
    bounds = []
    for i, (_, s, e) in enumerate(spans):
        if e > s:
            bounds.append((s, 1, i))
            bounds.append((e, 0, i))
    bounds.sort()
    # the timeline as (from, to, owner) pieces, end to end
    pieces: List[Tuple[float, float, str]] = []
    active: Dict[int, float] = {}
    at = float("-inf")
    for t, opens, i in bounds:
        if t > at:
            owner = (
                spans[max(active, key=active.get)][0] if active
                else UNATTRIBUTED
            )
            pieces.append((at, t, owner))
            at = t
        if opens:
            active[i] = spans[i][1]
        else:
            active.pop(i, None)
    pieces.append((at, float("inf"), UNATTRIBUTED))
    k = 0
    for lo, hi in gap_list:
        while pieces[k][1] <= lo:
            k += 1
        m = k
        while m < len(pieces) and pieces[m][0] < hi:
            a, b, owner = pieces[m]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[owner] = out.get(owner, 0.0) + part
            m += 1
    return out


def reduce(planes: Sequence[dict],
           window: Optional[Interval] = None) -> Optional[dict]:
    """→ {"window_s", "chips": {n: {"busy_s", "busy_share"}}, "busy_s"
    (mean over chips), "ops": {name: self s}, "programs": {name: s},
    "idle_by_span": {name: s}, "spans": {name: [count, s]}} or None when
    the trace holds no device plane.

    ``window`` defaults to the extent of the benchmark's host spans (the
    traced part of the run as the benchmark saw it), else of the device
    events. Operations are clipped to it."""
    devices = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            devices[int(m.group(1))] = plane
    if not devices:
        return None
    spans = host_spans(planes)
    if window is None:
        if spans:
            window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
        else:
            evs = [
                (s, s + d) for p in devices.values()
                for _, s, d in _line(p, OPS_LINE)
            ]
            if not evs:
                return None
            window = (min(s for s, _ in evs), max(e for _, e in evs))
    lo, hi = window
    window_s = hi - lo
    if window_s <= 0:
        return None
    ops: Dict[str, float] = {}
    programs: Dict[str, float] = {}
    chips: Dict[int, dict] = {}
    all_busy: List[Interval] = []
    for n, plane in sorted(devices.items()):
        op_events = _line(plane, OPS_LINE)
        busy = clip(union((s, s + d) for _, s, d in op_events), lo, hi)
        for name, s, d in self_times(op_events):
            if lo <= s < hi and d > 0:
                ops[name] = ops.get(name, 0.0) + d
        for name, s, d in _line(plane, MODULES_LINE):
            part = total(clip([(s, s + d)], lo, hi))
            if part > 0:
                programs[name] = programs.get(name, 0.0) + part
        busy_s = total(busy)
        chips[n] = {"busy_s": busy_s, "busy_share": busy_s / window_s}
        all_busy.extend(busy)
    # a gap is time in which NO chip ran an operation
    idle = attribute(gaps(union(all_busy), lo, hi), spans)
    span_totals: Dict[str, List[float]] = {}
    for name, s, e in spans:
        part = total(clip([(s, e)], lo, hi))
        entry = span_totals.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += part
    return {
        "window_s": window_s,
        "chips": chips,
        "busy_s": sum(c["busy_s"] for c in chips.values()) / len(chips),
        "ops": ops,
        "programs": programs,
        "idle_by_span": idle,
        "spans": span_totals,
    }


def short_name(name: str, limit: int = 96) -> str:
    """An operation as the trace names it, without its operands: the
    profiler gives a fusion its whole HLO line (``%fusion.7 = s32[...]
    fusion(...)``, kilobytes long); the part before `` = `` names it."""
    return name.split(" = ", 1)[0][:limit]


def top(table: Dict[str, float], n: int = 10) -> List[list]:
    """[[name, seconds], ...], the n largest, as the breakdown wants."""
    merged: Dict[str, float] = {}
    for name, secs in table.items():
        key = short_name(name)
        merged[key] = merged.get(key, 0.0) + secs
    return [
        [name, secs] for name, secs in
        sorted(merged.items(), key=lambda kv: -kv[1])[:n]
    ]


def program_us_per_lane(reduced: dict, pattern: str) -> Optional[float]:
    """Device microseconds of the programs matching ``pattern`` per lane
    the wire ledger saw reach the device between the trace's two edges
    (``reduced["counters"]``, which the harness attaches). None where
    there were no lanes or no such program."""
    from benchmark.lib import books

    edges = reduced["counters"]
    lanes = books.wire_lanes(edges["before"], edges["after"])
    secs = program_seconds(reduced, pattern)
    if lanes <= 0 or secs <= 0:
        return None
    return secs / lanes * 1e6


def program_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds of the programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for name, s in reduced["programs"].items() if rx.search(name))
