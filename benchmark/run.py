#!/usr/bin/env python3
"""One run of one benchmark cell on the TPU.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: it loads, builds the cell's data from ``--seed``
(on a thread, beside the node's start), starts the node the way an
operator does, waits for the canary on the device, warms the cell's own
shapes, measures for ``--seconds``, and prints one JSON object as the
last line of its standard output: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, and a profiler trace of a short
sub-window gives the device's busy seconds.

It exits non-zero and prints no result when jax finds no TPU or another
number of chips than the cell asks for. There is no CPU mode: the tests
rehearse the pieces at toy sizes.

This file knows no cell, configuration, generator or metric by name.
``BENCHMARK.json`` names the cell's configuration and traffic mix;
``workloads/<cell>.json`` lists the metrics it reports;
``traffic/<mix>.json`` names a generator (``traffic/<generator>.py``)
and holds its parameters; ``end_to_end/<metric>.py`` and
``layers/<metric>.py`` are one reader each.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

_T0 = time.monotonic()
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

NO_TPU_EXIT = 2


def say(msg: str) -> None:
    """Progress goes to stderr: stdout carries the result line only."""
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process was started, interpreter start-up and
    imports included (Linux: /proc); since this module loaded otherwise."""
    try:
        with open("/proc/self/stat") as fh:
            start_ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _T0


# --------------------------------------------------------------------------
# the manifest and the files it names


def load_json(*parts: str) -> dict:
    with open(os.path.join(_ROOT, *parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(_HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path
    )
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(workload: str) -> dict:
    """Everything one cell is made of, from the names in BENCHMARK.json."""
    manifest = load_json("BENCHMARK.json")
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == workload), None
    )
    if entry is None:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{[w['name'] for w in manifest['workloads']]}"
        )
    cfg_entry = next(
        c for c in manifest["configs"] if c["name"] == entry["config"]
    )
    traffic = load_json("benchmark", "traffic", entry["traffic"] + ".json")
    return {
        "name": workload,
        "chips": int(entry["chips"]),
        "config": load_json(*cfg_entry["file"].split("/")),
        "traffic": traffic,
        "cell": load_json("benchmark", "workloads", workload + ".json"),
        "generator": load_module("traffic", traffic["generator"]),
    }


# --------------------------------------------------------------------------
# the result line


def summarize(samples: dict, setup_s: float, cpu_s: float) -> dict:
    """What the end-to-end readers take: the window as the caller saw it.
    A failed request (wrong verdict, raised, timed out, answered by a
    fallback, or never answered) has no latency and verifies nothing."""
    ok = [(lat, sigs) for lat, sigs, status in samples["requests"]
          if status == "ok"]
    by_status: Dict[str, int] = {}
    for _, _, status in samples["requests"]:
        by_status[status] = by_status.get(status, 0) + 1
    attempted = int(samples["attempted"])
    return {
        "loop": samples["loop"],
        "setup_s": setup_s,
        "window_s": float(samples["window_s"]),
        "cpu_s": cpu_s,
        "cpu_units": samples.get("cpu_units", []),
        "attempted": attempted,
        "ok": len(ok),
        "failed": attempted - len(ok),
        "by_status": by_status,
        "mismatches": by_status.get("mismatch", 0)
        + int(samples.get("mismatches", 0)),
        "latency_ms": sorted(lat * 1e3 for lat, _ in ok),
        "latency_ms_in_order": [lat * 1e3 for lat, _ in ok],
        "sigs_verified": sum(s for _, s in ok) + int(samples["extra_sigs"]),
        "spans_s": samples.get("spans_s", {}),
        "late_s": samples.get("late_s", []),
    }


def result_line(run: dict, metrics: Dict[str, dict], device: dict,
                builds_in_window: int, expect_chips: int,
                breakdown: Optional[dict] = None) -> dict:
    """The contract's last line. ``correct`` is false on any verdict that
    differs from the reference, on any executable built inside the
    window, and on a platform or chip count that is not the cell's."""
    correct = (
        run["mismatches"] == 0
        and builds_in_window == 0
        and device.get("platform") == "tpu"
        and device.get("count") == expect_chips
    )
    line = {
        "correct": bool(correct),
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def read_metrics(kind: str, names: List[str], *args) -> Dict[str, dict]:
    """Each named reader's value; a reader that finds nothing to read
    returns None and its metric is left out."""
    out: Dict[str, dict] = {}
    for name in names:
        mod = load_module(kind, name)
        value = mod.read(*args)
        if value is None:
            say(f"{kind}/{name}: nothing to read")
            continue
        out[mod.NAME] = {"value": float(value), "unit": mod.UNIT}
    return out


# --------------------------------------------------------------------------
# tracing a sub-window


class SubWindowTrace:
    """Starts the profiler ``after_s`` into the window and stops it
    ``seconds`` later, both between two requests (``tick``), so that no
    request straddles an edge; reads the program's counters at both
    edges, so that lanes and device seconds cover the same requests. A
    generator whose serving thread must not stall (an open loop) never
    ticks: a watcher thread then makes each edge ``SLACK_S`` late."""

    SLACK_S = 1.0

    def __init__(self, plane, log_dir: str, after_s: float, seconds: float):
        self.plane = plane
        self.log_dir = log_dir
        self.after_s = after_s
        self.seconds = seconds
        self.t0: Optional[float] = None
        self.started_at: Optional[float] = None
        self.stopped = False
        self.before: Optional[dict] = None
        self.after: Optional[dict] = None
        self._lock = threading.Lock()

    def arm(self) -> None:
        self.t0 = time.monotonic()
        threading.Thread(target=self._watch, name="bench-trace-watch",
                         daemon=True).start()

    def _watch(self) -> None:
        time.sleep(self.after_s + self.SLACK_S)
        if self.started_at is None and not self.stopped:
            self._start()
        time.sleep(self.seconds + self.SLACK_S)
        self.stop()

    def tick(self) -> None:
        if self.stopped or self.t0 is None:
            return
        now = time.monotonic()
        if self.started_at is None:
            if now - self.t0 >= self.after_s:
                self._start()
        elif now - self.started_at >= self.seconds:
            self.stop()

    def _start(self) -> None:
        import jax

        with self._lock:
            if self.started_at is not None or self.stopped:
                return
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            self.before = self.plane.books.snapshot()
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            self.started_at = time.monotonic()
            self.plane.span = jax.profiler.TraceAnnotation

    def stop(self) -> None:
        import jax

        with self._lock:
            if self.stopped or self.started_at is None:
                self.stopped = True
                return
            self.stopped = True
            self.plane.span = lambda name: contextlib.nullcontext()
            self.after = self.plane.books.snapshot()
            jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        """The trace as numbers (trace_reduce.py), with the counters'
        difference over the same sub-window under "counters"."""
        from benchmark import trace_reduce

        if self.before is None or self.after is None:
            return None
        path = trace_reduce.find_xplane(self.log_dir)
        if path is None:
            return None
        reduced = trace_reduce.reduce(trace_reduce.load(path))
        if reduced is None:
            return None
        reduced["counters"] = {"before": self.before, "after": self.after}
        return reduced


# --------------------------------------------------------------------------


def gate(expect_chips: int) -> Optional[dict]:
    """The device as jax reports it, or None (said on stderr) when it is
    not a TPU with the chips the cell asks for."""
    from benchmark.lib import plane as planelib

    dev = planelib.device_record()
    if dev["platform"] != "tpu" or dev["count"] != expect_chips:
        print(
            f"benchmark: this cell needs {expect_chips} TPU chip(s); jax "
            f"found platform {dev['platform']!r}, {dev['kind']}, "
            f"{dev['count']} device(s); nothing was run",
            file=sys.stderr,
        )
        return None
    return dev


def count_jax_compiles() -> Dict[str, int]:
    """A counter of every program jax's backend builds from here on,
    executables served by the persistent cache included."""
    import jax

    seen = {"n": 0}

    def on_duration(name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, expect_platform: str = "tpu") -> dict:
    """One run of ``cell`` (as resolve_cell gives it). The command always
    expects a TPU; the tests rehearse toy cells on the CPU platform."""
    from cometbft_tpu.crypto.tpu import aot

    from benchmark.lib import loops
    from benchmark.lib import plane as planelib

    gen = cell["generator"]
    cache_dir = aot.compile_cache_dir()
    say(f"device {device}; compile cache {cache_dir}")
    jax_compiles = count_jax_compiles()

    built: dict = {}

    def build() -> None:
        try:
            built["plan"] = gen.build(
                cell["config"], cell["traffic"]["params"], seed
            )
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            built["error"] = exc

    builder = threading.Thread(target=build, name="bench-build")
    builder.start()
    tmp = tempfile.mkdtemp(prefix="bench_home_")
    plane = None
    try:
        plane = planelib.start(
            os.path.join(tmp, "home"), cell["config"].get("crypto", {}),
            expect_platform,
        )
        say(f"node up: {plane.started_s}")
        builder.join()
        if "error" in built:
            raise built["error"]
        plan = built["plan"]
        say("data built; warming the cell's shapes")
        warmed = gen.warm(plane, plan)
        for b in plane.books.aot_builds():
            say(f"built {b['kernel']}@{b['bucket']} sharded={b['sharded']} "
                f"from {b['source']} in {b['seconds']:.1f}s")
        say(f"warm: {warmed}; jax backend builds so far {jax_compiles['n']}")

        tracer = None
        if trace:
            spec = cell["cell"].get("trace", {})
            tracer = SubWindowTrace(
                plane, os.path.join(tmp, "trace"),
                float(spec.get("after_s", 2.0)),
                float(spec.get("seconds", 3.0)),
            )
            plane.tick = tracer.tick
        before = plane.books.snapshot()
        jax_before = jax_compiles["n"]
        cpu_before = loops.cpu_seconds()
        setup_s = process_age_s()
        say(f"set-up {setup_s:.1f}s; measuring for {seconds:g}s")
        if tracer is not None:
            tracer.arm()
        samples = gen.drive(plane, plan, seconds)
        cpu_s = loops.cpu_seconds() - cpu_before
        if tracer is not None:
            tracer.stop()
        after = plane.books.snapshot()
        builds = max(
            after["aot_builds"] - before["aot_builds"],
            jax_compiles["n"] - jax_before,
        )
        run = summarize(samples, setup_s, cpu_s)
        say(f"window {run['window_s']:.2f}s: attempted {run['attempted']}, "
            f"by status {run['by_status']}, builds in window {builds}, "
            f"process CPU {cpu_s:.2f}s for {run['sigs_verified']} signatures")
        say(f"counters that moved: {_moved(before, after)}")
        device = dict(device, memory_peak_bytes=planelib.memory_peak_bytes())
        if not trace:
            metrics = read_metrics("end_to_end", cell["cell"]["end_to_end"],
                                   run)
            return result_line(run, metrics, device, builds, cell["chips"])
        reduced = tracer.reduce()
        after["bench"] = dict(run, builds_in_window=builds,
                              device_kind=device["kind"])
        metrics = read_metrics("layers", cell["cell"]["layers"],
                               before, after, reduced)
        breakdown = None
        if reduced is not None:
            from benchmark import trace_reduce

            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": trace_reduce.top(reduced["ops"]),
                "idle_gaps": trace_reduce.top(reduced["idle_by_span"]),
            }
            say(f"trace: window {reduced['window_s']:.3f}s, chips "
                f"{reduced['chips']}, programs "
                f"{trace_reduce.top(reduced['programs'], 5)}")
        return result_line(run, metrics, device, builds, cell["chips"],
                           breakdown)
    finally:
        if plane is not None:
            plane.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def _moved(before: dict, after: dict) -> dict:
    """The counters' differences over the window, zeros left out: what
    the program did, for whoever reads the run's stderr."""
    out: dict = {}
    for key, val in after.items():
        if isinstance(val, dict):
            sub = _moved(before.get(key) or {}, val)
            if sub:
                out[key] = sub
        elif isinstance(val, (int, float)):
            diff = val - (before.get(key) or 0)
            if diff:
                out[key] = round(diff, 6)
        elif val != before.get(key):
            out[key] = val
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve_cell(args.workload)
    device = gate(cell["chips"])
    if device is None:
        return NO_TPU_EXIT
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    # Threads of a node that a failed run left half-stopped must not keep
    # the process alive, and no process is ever started: the exit is hard
    # either way. A failure prints its traceback and no result line.
    try:
        rc = main()
    except SystemExit as exc:
        if isinstance(exc.code, int):
            rc = exc.code
        else:
            print(exc.code, file=sys.stderr)
            rc = 1
    except BaseException:  # noqa: BLE001 - report, then exit non-zero
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
