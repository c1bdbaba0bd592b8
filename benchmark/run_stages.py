#!/usr/bin/env python3
"""One traced run of one cell, with the program's stages in its line.

    python3 benchmark/run_stages.py --workload <cell> --seed <n> --seconds <s>

``run.py --trace 1`` as it is, but the traced sub-window is reduced by
``stage_reduce.reduce``: ``breakdown.idle_gaps`` names the ``cbft:``
stage that covers each gap, ``metrics`` gains the stage metrics the
trace can give (``stage_reduce.metrics``) and the line a ``stages``
table (``{name: [count, seconds]}``, with ``bench_s``, ``unstaged_s``
and the ``bench:`` spans beside it for the cross-check, and what the
trace cost: events, bytes, seconds to stop the profiler). The driver
does not run this file: it is the builder's way to a stage breakdown
until ``run.py`` reads the stages itself (PERF.md section 7).
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run, stage_reduce, trace_reduce  # noqa: E402


class StageTrace(run.SubWindowTrace):
    last = None  # the newest reduction, for main() below
    stop_s = 0.0  # what stopping the profiler (collection) took

    def stop(self) -> None:
        t0 = run.time.monotonic()
        was_running = self.started_at is not None and not self.stopped
        super().stop()
        if was_running:
            StageTrace.stop_s = run.time.monotonic() - t0

    def reduce(self):
        if self.before is None or self.after is None:
            return None
        path = trace_reduce.find_xplane(self.log_dir)
        if path is None:
            return None
        planes = trace_reduce.load(path)
        reduced = stage_reduce.reduce(planes)
        if reduced is None:
            return None
        reduced["counters"] = {"before": self.before, "after": self.after}
        reduced["trace"] = {
            "events": sum(len(line["events"]) for p in planes
                          for line in p["lines"]),
            "bytes": os.path.getsize(path),
            "stop_s": StageTrace.stop_s,
        }
        StageTrace.last = reduced
        return reduced


def main(argv=None) -> int:
    ap = run.argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.resolve_cell(args.workload)
    device = run.gate(cell["chips"])
    if device is None:
        return run.NO_TPU_EXIT
    run.SubWindowTrace = StageTrace
    line = run.run_cell(cell, args.seed, args.seconds, True, device)
    reduced = StageTrace.last
    if reduced is not None:
        line["metrics"].update(stage_reduce.metrics(reduced))
        line["stages"] = {
            "stages": reduced["stages"],
            "spans": reduced["spans"],
            "bench_s": reduced["bench_s"],
            "unstaged_s": reduced["unstaged_s"],
            "idle_gaps": trace_reduce.top(reduced["idle_by_span"], 24),
            "trace": reduced["trace"],
        }
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
