#!/usr/bin/env python3
"""The program's request-path stages in a profiler trace.

    python3 benchmark/stage_reduce.py <capture dir | .xplane.pb | .pbtxt>

``cometbft_tpu/libs/trace.stage`` writes every stage between a request's
submit and its verdict into a running profiler session as a host
annotation named ``cbft:<layer>.<stage>``: on the clock of the device's
operations, beside the benchmark's own ``bench:`` spans. This module
reads them with ``trace_reduce``'s pieces and adds to its reduction:

* ``stages``: ``{name: [count, seconds]}`` of the ``cbft:`` spans,
  clipped to the window;
* ``bench_s``: seconds of the union of the ``bench:`` spans (the time a
  request was open), and ``unstaged_s``: the part of it that no stage on
  any thread covers (queue wait, thread handoff, glue between layers);
* ``idle_by_span`` attributed again, over the spans of BOTH prefixes,
  so that a gap goes to the program stage that caused it and not to the
  benchmark span around the whole call.

The window stays what ``trace_reduce.reduce`` makes it, the extent of
the ``bench:`` spans, and every other key keeps its value. ``metrics``
turns the stages into the six per-layer numbers of PERF.md section 3.

Pitfall, inherited from ``trace_reduce.attribute``: a gap goes to the
span opened LAST on ANY thread. That is the innermost stage where one
thread serves the request; a long span of background work opened later
on another thread would take the gaps of the stages that caused them,
which is why the program annotates request-path stages only.

This is not wired into ``run.py`` (see PERF.md section 7 for the edit
that would): ``run_stages.py`` runs a cell with it, and the command
above reduces any capture, an operator's ``ProfilerCapture`` included
(no ``bench:`` span there: the window is the extent of the device's
operations and ``unstaged_s`` is 0).

No jax import at module level and no device call anywhere.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import trace_reduce  # noqa: E402

STAGE_PREFIX = "cbft:"

Span = Tuple[str, float, float]

# name, unit, layer (as PERF.md section 3 has it), the stages whose
# seconds are added up, the stage whose count divides them. Each is a
# mean per occurrence of the dividing stage, in ms; all "lower is better",
# source program_span, moving verdict_p50_ms.
PER_STAGE_MS = (
    ("commit_sign_bytes_ms", "types.validator_set",
     ("commit.sign_bytes",), "commit.sign_bytes"),
    # per commit, the chunks' blocked waits summed
    ("resident_wait_ms", "crypto.tpu.ed25519_batch",
     ("resident.retire",), "commit.sign_bytes"),
    ("flush_host_ms", "crypto.scheduler",
     ("sched.assemble", "sched.route", "sched.demux"), "sched.assemble"),
    ("host_verify_ms", "crypto.batch",
     ("host.verify",), "host.verify"),
    # per device dispatch, the chunks' blocked waits summed
    ("mesh_wait_ms", "crypto.tpu.mesh",
     ("mesh.retire",), "sup.device"),
)
UNSTAGED = ("unstaged_share", "between-layers")


def stage_spans(planes: Sequence[dict]) -> List[Span]:
    """(name, start_s, end_s) of every program stage, by start."""
    out = []
    for plane in planes:
        if plane["name"] != trace_reduce.HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(STAGE_PREFIX):
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda t: t[1])


def _intervals(spans: Sequence[Span]) -> List[trace_reduce.Interval]:
    return trace_reduce.union((s, e) for _, s, e in spans)


def reduce(planes: Sequence[dict]) -> Optional[dict]:
    """``trace_reduce.reduce(planes)`` plus "stages", "bench_s",
    "unstaged_s" and the finer "idle_by_span"; None where that is."""
    bench = trace_reduce.host_spans(planes)
    reduced = trace_reduce.reduce(planes)
    if reduced is None:
        return None
    stages = stage_spans(planes)
    busy = trace_reduce.union(
        (s, s + d) for plane in planes
        if trace_reduce.DEVICE_PLANE.match(plane["name"])
        for _, s, d in trace_reduce._line(plane, trace_reduce.OPS_LINE)
    )
    if bench:
        lo = min(s for _, s, _ in bench)
        hi = max(e for _, _, e in bench)
    else:
        lo, hi = busy[0][0], busy[-1][1]
    totals: Dict[str, List[float]] = {}
    for name, s, e in stages:
        part = trace_reduce.total(trace_reduce.clip([(s, e)], lo, hi))
        if part > 0:
            entry = totals.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += part
    open_s = trace_reduce.clip(_intervals(bench), lo, hi)
    unstaged = [
        gap for s, e in open_s
        for gap in trace_reduce.gaps(
            trace_reduce.clip(_intervals(stages), s, e), s, e)
    ]
    reduced["stages"] = totals
    reduced["bench_s"] = trace_reduce.total(open_s)
    reduced["unstaged_s"] = trace_reduce.total(unstaged)
    reduced["idle_by_span"] = trace_reduce.attribute(
        trace_reduce.gaps(trace_reduce.clip(busy, lo, hi), lo, hi),
        sorted(bench + stages, key=lambda t: t[1]),
    )
    return reduced


def metrics(reduced: Optional[dict]) -> Dict[str, dict]:
    """{name: {"value", "unit"}} of the stage metrics this trace can
    give; one whose stages the trace does not hold is left out."""
    out: Dict[str, dict] = {}
    if not reduced or "stages" not in reduced:
        return out
    stages = reduced["stages"]
    for name, _layer, summed, per in PER_STAGE_MS:
        parts = [stages.get(STAGE_PREFIX + s) for s in summed]
        count = stages.get(STAGE_PREFIX + per, [0, 0.0])[0]
        if not count or not any(parts):
            continue
        secs = sum(p[1] for p in parts if p)
        out[name] = {"value": secs / count * 1e3, "unit": "ms"}
    if stages and reduced["bench_s"] > 0:
        out[UNSTAGED[0]] = {
            "value": reduced["unstaged_s"] / reduced["bench_s"] * 100.0,
            "unit": "%",
        }
    return out


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = args[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path) or path
    reduced = reduce(trace_reduce.load(path))
    if reduced is None:
        print(f"{path}: no device plane in this trace", file=sys.stderr)
        return 1
    print(json.dumps({
        "window_s": reduced["window_s"],
        "busy_s": reduced["busy_s"],
        "bench_s": reduced["bench_s"],
        "unstaged_s": reduced["unstaged_s"],
        "spans": reduced["spans"],
        "stages": reduced["stages"],
        "idle_gaps": trace_reduce.top(reduced["idle_by_span"], 20),
        "metrics": metrics(reduced),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
