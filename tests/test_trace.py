"""Verify-path tracing: span core, flight recorder, exporters, the
instrumented scheduler/supervisor/mesh pipeline, incident dumps, and the
tools/trace_report.py CLI.

The end-to-end acceptance test drives a REAL TPU-kernel dispatch (on the
virtual CPU-device mesh the conftest configures) through scheduler →
supervisor → mesh so the recorded trace carries request → dispatch →
supervise → device → chunk nesting with nonzero device-time attribution,
then trips the watchdog to produce the automatic flight-recorder dump
and renders it through the report CLI and the Chrome exporter.
"""

import glob
import importlib.util
import json
import os
import threading
import time

import pytest

from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.libs.metrics import Registry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dumps(dirpath, reason="watchdog"):
    """Incident dump files for ``reason`` in ``dirpath``, oldest first
    (filenames embed a nanosecond timestamp, so name order = time order)."""
    return sorted(
        glob.glob(os.path.join(str(dirpath), f"trace_dump_{reason}_*.json"))
    )


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_REPO, "tools", "trace_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mk_items(n=4, secret=b"trace-test"):
    from cometbft_tpu.crypto import ed25519 as ed

    k = ed.gen_priv_key_from_secret(secret)
    m = b"trace test message"
    sig = k.sign(m)
    return [(k.pub_key(), m, sig)] * n


# ---------------------------------------------------------------------------
# span core


class TestSpanCore:
    def test_lifecycle_nesting_and_parent_ids(self):
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        root = tr.start_span("request", n_sigs=4)
        assert not root.noop
        child = root.child("dispatch", reason="explicit")
        grand = child.child("chunk", chunk=0)
        assert child.trace_id == root.trace_id == grand.trace_id
        grand.end()
        child.end()
        assert tr.recent() == []  # trace completes only when the ROOT ends
        root.end(ok=True)
        traces = tr.recent()
        assert len(traces) == 1
        spans = traces[0]["spans"]
        assert [s["name"] for s in spans] == ["request", "dispatch", "chunk"]
        by_name = {s["name"]: s for s in spans}
        assert by_name["request"]["parent_id"] is None
        assert by_name["dispatch"]["parent_id"] == by_name["request"]["span_id"]
        assert by_name["chunk"]["parent_id"] == by_name["dispatch"]["span_id"]
        assert by_name["request"]["tags"] == {"n_sigs": 4, "ok": True}
        assert all(s["dur_us"] >= 0 for s in spans)

    def test_context_manager_tags_errors(self):
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        with pytest.raises(RuntimeError):
            with tr.start_span("request") as sp:
                sp.set_tag("k", "v")
                raise RuntimeError("boom")
        (trace,) = tr.recent()
        tags = trace["spans"][0]["tags"]
        assert tags["k"] == "v"
        assert "boom" in tags["error"]

    def test_end_is_idempotent_first_wins(self):
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        sp = tr.start_span("request")
        sp.end(outcome="first")
        sp.end(outcome="second")
        (trace,) = tr.recent()
        assert trace["spans"][0]["tags"]["outcome"] == "first"
        assert len(tr.recent()) == 1  # no double-complete

    def test_ring_buffer_eviction(self):
        tr = tracelib.Tracer(sample=1.0, buffer=4)
        for i in range(10):
            tr.start_span("request", i=i).end()
        traces = tr.recent()
        assert len(traces) == 4
        # newest first, oldest evicted
        assert [t["spans"][0]["tags"]["i"] for t in traces] == [9, 8, 7, 6]

    def test_straggler_ending_after_root_is_dropped(self):
        tr = tracelib.Tracer(sample=1.0, buffer=4)
        root = tr.start_span("request")
        zombie = root.child("chunk")
        root.end()
        zombie.end()  # late: its trace already completed
        (trace,) = tr.recent()
        assert [s["name"] for s in trace["spans"]] == ["request"]

    def test_sampling_zero_is_noop_fast_path(self):
        tr = tracelib.Tracer(sample=0.0, buffer=8)
        sp = tr.start_span("request", n_sigs=4)
        assert sp is tracelib.NOOP_SPAN
        assert sp.child("dispatch") is tracelib.NOOP_SPAN
        sp.set_tag("k", "v")
        sp.end()
        assert tr.recent() == []
        assert tr.n_started == 0

    def test_sampling_fraction_deterministic(self):
        tr = tracelib.Tracer(sample=0.5, buffer=1024, seed=7)
        for _ in range(200):
            tr.start_span("request").end()
        n = len(tr.recent())
        assert 0 < n < 200
        assert n == tr.n_started

    def test_child_through_explicit_parent_ignores_sampling(self):
        # once a root is sampled, children always record regardless of
        # the sampling fraction
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        root = tr.start_span("request")
        child = tr.start_span("dispatch", parent=root)
        child.end()
        root.end()
        (trace,) = tr.recent()
        assert len(trace["spans"]) == 2

    def test_thread_safety(self):
        tr = tracelib.Tracer(sample=1.0, buffer=64)
        errs = []

        def work(tid):
            try:
                for i in range(50):
                    root = tr.start_span("request", tid=tid, i=i)
                    with tracelib.use(root):
                        tracelib.child_of_current("dispatch").end()
                    root.end()
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        ts = [threading.Thread(target=work, args=(t,)) for t in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        traces = tr.recent()
        assert len(traces) == 64  # buffer full, 8*50 completed total
        assert tr.n_completed == 400
        for t in traces:
            assert [s["name"] for s in t["spans"]] == ["request", "dispatch"]

    def test_use_and_child_of_current(self):
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        assert tracelib.current_span() is None
        assert tracelib.child_of_current("x") is tracelib.NOOP_SPAN
        root = tr.start_span("request")
        with tracelib.use(root):
            assert tracelib.current_span() is root
            child = tracelib.child_of_current("dispatch")
            assert child.parent_id == root.span_id
            with tracelib.use(child):
                assert tracelib.current_span() is child
            assert tracelib.current_span() is root
            child.end()
        assert tracelib.current_span() is None
        root.end()

    def test_noop_current_span_yields_noop_children(self):
        with tracelib.use(tracelib.NOOP_SPAN):
            assert tracelib.child_of_current("chunk") is tracelib.NOOP_SPAN

    def test_tracer_span_roots_when_no_current(self):
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        sp = tr.span("supervise")
        assert sp.parent_id is None
        sp.end()
        assert len(tr.recent()) == 1


# ---------------------------------------------------------------------------
# knobs + exporters


class TestKnobsAndExporters:
    def test_sample_knob_precedence(self, monkeypatch):
        monkeypatch.delenv("CBFT_TRACE_SAMPLE", raising=False)
        assert tracelib.trace_sample_default() == 0.0
        assert tracelib.trace_sample_default(0.25) == 0.25
        monkeypatch.setenv("CBFT_TRACE_SAMPLE", "0.75")
        assert tracelib.trace_sample_default(0.25) == 0.75
        monkeypatch.setenv("CBFT_TRACE_SAMPLE", "junk")
        assert tracelib.trace_sample_default(0.25) == 0.25

    def test_buffer_knob_precedence(self, monkeypatch):
        monkeypatch.delenv("CBFT_TRACE_BUFFER", raising=False)
        assert tracelib.trace_buffer_default() == tracelib.DEFAULT_BUFFER
        assert tracelib.trace_buffer_default(32) == 32
        monkeypatch.setenv("CBFT_TRACE_BUFFER", "8")
        assert tracelib.trace_buffer_default(32) == 8

    def test_config_trace_knobs_roundtrip_and_validation(self, tmp_path):
        from cometbft_tpu.config import (
            Config,
            load_config_file,
            write_config_file,
        )

        cfg = Config()
        cfg.instrumentation.trace_sample = 0.125
        cfg.instrumentation.trace_buffer = 64
        cfg.validate_basic()
        path = str(tmp_path / "config.toml")
        write_config_file(path, cfg)
        # floats must survive TOML round-trip AS floats (regression: the
        # writer used to quote them into strings)
        loaded = load_config_file(path)
        assert loaded.instrumentation.trace_sample == 0.125
        assert loaded.instrumentation.trace_buffer == 64
        loaded.validate_basic()
        for bad in (-0.1, 1.5, "half", True):
            cfg.instrumentation.trace_sample = bad
            with pytest.raises(ValueError):
                cfg.validate_basic()
        cfg.instrumentation.trace_sample = 0.5
        for bad in (0, -1, "many", 1.5):
            cfg.instrumentation.trace_buffer = bad
            with pytest.raises(ValueError):
                cfg.validate_basic()

    def test_chrome_trace_schema(self):
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        root = tr.start_span("request", n_sigs=4, blob=b"\x00")
        root.child("dispatch").end()
        root.end()
        doc = tracelib.chrome_trace(tr.recent())
        # must be valid JSON end to end (bytes tags coerced)
        parsed = json.loads(json.dumps(doc))
        assert parsed["displayTimeUnit"] == "ms"
        events = parsed["traceEvents"]
        xevents = [e for e in events if e["ph"] == "X"]
        assert len(xevents) == 2
        for e in xevents:
            for key in ("name", "cat", "ph", "pid", "tid", "ts", "dur", "args"):
                assert key in e, key
            assert e["dur"] > 0
        # the child is time-contained in the root (how "X" events nest)
        byname = {e["name"]: e for e in xevents}
        req, dis = byname["request"], byname["dispatch"]
        assert req["ts"] <= dis["ts"]
        assert dis["ts"] + dis["dur"] <= req["ts"] + req["dur"] + 0.01

    def test_stage_histogram_in_registry_expose(self):
        reg = Registry()
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        tracelib.attach_stage_metrics(tr, reg)
        root = tr.start_span("request")
        root.child("dispatch").end()
        root.end()
        text = reg.expose()
        assert "verify_trace_stage_seconds_bucket" in text
        assert 'stage="request"' in text
        assert 'stage="dispatch"' in text
        assert 'verify_trace_stage_seconds_count{stage="request"} 1' in text

    def test_dump_to_configured_dir_and_env_override(self, tmp_path, monkeypatch):
        monkeypatch.delenv("CBFT_TRACE_DUMP_DIR", raising=False)
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        tr.start_span("request").end()
        assert tr.dump("nowhere") is None  # no destination configured
        tr.set_dump_dir(str(tmp_path / "cfg"))
        p1 = tr.dump("watchdog")
        assert p1 in _dumps(tmp_path / "cfg")
        doc = json.load(open(p1))
        assert doc["reason"] == "watchdog"
        assert len(doc["traces"]) == 1
        envdir = tmp_path / "env"
        monkeypatch.setenv("CBFT_TRACE_DUMP_DIR", str(envdir))
        p2 = tr.dump("watchdog")
        assert p2 in _dumps(envdir)
        assert _dumps(tmp_path / "cfg")  # cfg dump untouched

    def test_dump_retention_keeps_newest_n(self, tmp_path, monkeypatch):
        monkeypatch.delenv("CBFT_TRACE_DUMP_DIR", raising=False)
        monkeypatch.delenv("CBFT_TRACE_DUMP_KEEP", raising=False)
        tr = tracelib.Tracer(sample=1.0, buffer=8, dump_keep=3)
        tr.start_span("request").end()
        tr.set_dump_dir(str(tmp_path))
        paths = [tr.dump(f"cause{i}") for i in range(6)]
        assert all(paths)
        left = sorted(
            glob.glob(str(tmp_path / "trace_dump_*.json"))
        )
        assert len(left) == 3
        # the newest three survived, oldest three were pruned
        assert set(left) == set(paths[-3:])

    def test_dump_keep_env_overrides(self, monkeypatch):
        monkeypatch.setenv("CBFT_TRACE_DUMP_KEEP", "7")
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        assert tr.dump_keep == 7
        monkeypatch.delenv("CBFT_TRACE_DUMP_KEEP")
        assert tracelib.Tracer(sample=0).dump_keep == (
            tracelib.DEFAULT_DUMP_KEEP
        )

    def test_explicit_path_write_does_not_prune(self, tmp_path):
        tr = tracelib.Tracer(sample=1.0, buffer=8, dump_keep=1)
        tr.start_span("request").end()
        tr.set_dump_dir(str(tmp_path))
        auto = tr.dump("auto")
        assert auto and os.path.exists(auto)
        # an explicit-path write is caller-owned: verbatim filename, no
        # retention sweep of the surrounding directory
        pinned = str(tmp_path / "trace_dump_pinned.json")
        assert tr.dump("pinned", path=pinned) == pinned
        assert os.path.exists(auto)


# ---------------------------------------------------------------------------
# scheduler integration


class TestSchedulerTracing:
    def _scheduler(self, tracer, **kw):
        from cometbft_tpu.crypto.scheduler import VerifyScheduler

        kw.setdefault("flush_us", 100)
        return VerifyScheduler(spec="cpu", tracer=tracer, **kw)

    def test_request_and_dispatch_spans(self):
        tr = tracelib.Tracer(sample=1.0, buffer=16)
        sched = self._scheduler(tr)
        sched.start()
        try:
            fut = sched.submit(_mk_items(3), subsystem="consensus", height=42)
            ok, _ = fut.result(timeout=10)
            assert ok
        finally:
            sched.stop()
        traces = [
            t for t in tr.recent()
            if any(s["name"] == "dispatch" for s in t["spans"])
        ]
        assert traces
        spans = {s["name"]: s for s in traces[0]["spans"]}
        req = spans["request"]
        assert req["tags"]["n_sigs"] == 3
        assert req["tags"]["subsystem"] == "consensus"
        assert req["tags"]["height"] == 42
        assert req["tags"]["ok"] is True
        assert "wait_us" in req["tags"]
        dis = spans["dispatch"]
        assert dis["parent_id"] == req["span_id"]
        assert dis["tags"]["reason"] in (
            "deadline", "size", "explicit", "drain", "broken"
        )
        assert dis["tags"]["n_sigs"] == 3
        assert 0 < dis["tags"]["lane_fill"] <= 1.0

    def test_coalesced_requests_link_to_dispatch(self):
        tr = tracelib.Tracer(sample=1.0, buffer=16)
        sched = self._scheduler(tr, flush_us=50_000)
        sched.start()
        try:
            f1 = sched.submit(_mk_items(2))
            f2 = sched.submit(_mk_items(2))
            sched.flush()
            f1.result(timeout=10)
            f2.result(timeout=10)
        finally:
            sched.stop()
        traces = tr.recent()
        hosts = [
            t for t in traces
            if any(s["name"] == "dispatch" for s in t["spans"])
        ]
        riders = [
            t for t in traces
            if t["spans"]
            and t["spans"][0]["name"] == "request"
            and "dispatch_span" in t["spans"][0]["tags"]
        ]
        # one request hosted the dispatch span; the coalesced sibling
        # links to it by tag (spans form a tree, traces stay separate)
        assert len(hosts) == 1
        assert len(riders) == 1
        did = hosts[0]
        dispatch_id = next(
            s["span_id"] for s in did["spans"] if s["name"] == "dispatch"
        )
        assert riders[0]["spans"][0]["tags"]["dispatch_span"] == dispatch_id

    def test_disabled_mode_records_nothing(self):
        tr = tracelib.Tracer(sample=0.0, buffer=16)
        sched = self._scheduler(tr)
        sched.start()
        try:
            for _ in range(3):
                ok, _ = sched.submit(_mk_items(2)).result(timeout=10)
                assert ok
        finally:
            sched.stop()
        assert tr.recent() == []
        assert tr.n_started == 0  # the no-op path never allocated a span

    def test_empty_submit_and_inline_dispatch_spans(self):
        tr = tracelib.Tracer(sample=1.0, buffer=16)
        sched = self._scheduler(tr)  # NOT started: inline dispatch path
        ok, mask = sched.submit(_mk_items(2)).result(timeout=5)
        assert ok and mask == [True, True]
        ok, mask = sched.submit([]).result(timeout=5)
        assert ok and mask == []
        names = [
            s["name"] for t in tr.recent() for s in t["spans"]
        ]
        assert names.count("request") == 2
        assert names.count("dispatch") == 1  # empty submit never dispatches


# ---------------------------------------------------------------------------
# supervisor integration + incident dumps


class TestSupervisorTracing:
    def test_watchdog_trip_writes_flight_recorder_dump(self, tmp_path):
        from cometbft_tpu.crypto import faults
        from cometbft_tpu.crypto.supervisor import BackendSupervisor

        tr = tracelib.Tracer(sample=1.0, buffer=16)
        tr.set_dump_dir(str(tmp_path))
        plan = faults.install(
            "trace-wd", inner="cpu", plan=faults.FaultPlan()
        )
        sup = BackendSupervisor(
            spec="trace-wd",
            dispatch_timeout_ms=200,
            audit_pct=0,
            tracer=tr,
        )
        items = _mk_items(4)
        # healthy dispatch first so the recorder has a completed trace
        assert sup.verify_items(items) == [True] * 4
        plan.hang_rate = 1.0
        plan.hang_s = 30.0
        mask = sup.verify_items(items)  # watchdog fires; CPU fallback
        assert mask == [True] * 4
        assert sup.state() == "broken"
        dumps = _dumps(tmp_path)
        assert dumps
        doc = json.load(open(dumps[-1]))
        assert doc["reason"] == "watchdog"
        assert doc["traces"]  # the healthy dispatch made it in
        # the dump is written at trip time, so it holds the COMPLETED
        # healthy trace (the hanging request's root is still open)
        names = {
            s["name"] for t in doc["traces"] for s in t["spans"]
        }
        assert {"supervise", "device"} <= names
        sup.stop()
        plan.clear()

    def test_supervise_span_outcomes(self):
        from cometbft_tpu.crypto import faults
        from cometbft_tpu.crypto.supervisor import BackendSupervisor

        tr = tracelib.Tracer(sample=1.0, buffer=16)
        plan = faults.install(
            "trace-outcome", inner="cpu",
            plan=faults.FaultPlan(exception_rate=1.0),
        )
        sup = BackendSupervisor(
            spec="trace-outcome",
            breaker_threshold=1,
            audit_pct=0,
            tracer=tr,
        )
        items = _mk_items(2)
        assert sup.verify_items(items) == [True, True]  # fails → CPU
        assert sup.state() == "broken"
        assert sup.verify_items(items) == [True, True]  # broken → routed
        outcomes = [
            t["spans"][0]["tags"].get("outcome")
            for t in tr.recent()
            if t["spans"][0]["name"] == "supervise"
        ]
        assert "failure_cpu" in outcomes
        assert "cpu_routed" in outcomes
        sup.stop()
        plan.clear()


# ---------------------------------------------------------------------------
# end-to-end acceptance: TPU dispatch nesting + dump + chrome + report


class TestEndToEnd:
    def test_tpu_trace_dump_chrome_export_and_report(self, tmp_path, capsys):
        from cometbft_tpu.crypto import faults
        from cometbft_tpu.crypto.batch import BackendSpec
        from cometbft_tpu.crypto.scheduler import VerifyScheduler
        from cometbft_tpu.crypto.supervisor import BackendSupervisor

        tracer = tracelib.Tracer(sample=1.0, buffer=64)
        tracer.set_dump_dir(str(tmp_path))

        # 1. a traced coalesced dispatch through the REAL device path
        #    (virtual CPU-device mesh; min_batch=1 forces device routing)
        spec = BackendSpec(name="tpu", min_batch=1)
        sup = BackendSupervisor(spec=spec, audit_pct=0, tracer=tracer)
        sched = VerifyScheduler(
            spec=spec, supervisor=sup, tracer=tracer, flush_us=100
        )
        sched.start()
        try:
            fut = sched.submit(
                _mk_items(8), subsystem="blocksync", height=11
            )
            ok, mask = fut.result(timeout=300)
            assert ok and mask == [True] * 8
        finally:
            sched.stop()
            sup.stop()

        # 2. watchdog trip through a hanging backend sharing the SAME
        #    tracer → automatic flight-recorder dump includes the device
        #    trace recorded above
        plan = faults.install(
            "trace-e2e", inner="cpu",
            plan=faults.FaultPlan(hang_rate=1.0, hang_s=30.0),
        )
        sup2 = BackendSupervisor(
            spec="trace-e2e",
            dispatch_timeout_ms=150,
            audit_pct=0,
            tracer=tracer,
        )
        assert sup2.verify_items(_mk_items(2)) == [True, True]
        assert sup2.state() == "broken"
        sup2.stop()
        plan.clear()

        dumps = _dumps(tmp_path)
        assert dumps
        dump_path = dumps[-1]
        doc = json.load(open(dump_path))
        assert doc["reason"] == "watchdog"

        # request → dispatch → supervise → device → chunk parent chain
        # with nonzero device-time attribution
        target = None
        for t in doc["traces"]:
            names = {s["name"] for s in t["spans"]}
            if {"request", "dispatch", "device", "chunk"} <= names:
                target = t
                break
        assert target is not None, "no fully-nested device trace in dump"
        by_id = {s["span_id"]: s for s in target["spans"]}
        chunk = next(s for s in target["spans"] if s["name"] == "chunk")
        chain = [chunk["name"]]
        cur = chunk
        while cur["parent_id"] is not None:
            cur = by_id[cur["parent_id"]]
            chain.append(cur["name"])
        assert chain == [
            "chunk", "device", "supervise", "dispatch", "request"
        ]
        assert chunk["tags"]["device_wait_ns"] > 0
        assert chunk["tags"]["host_ns"] > 0
        req = next(s for s in target["spans"] if s["name"] == "request")
        assert req["tags"]["subsystem"] == "blocksync"
        assert req["tags"]["height"] == 11

        # Chrome export: valid trace-event JSON, chunk time-contained in
        # its dispatch on the same tid
        chrome = tracelib.chrome_trace(doc["traces"])
        parsed = json.loads(json.dumps(chrome))
        assert parsed["traceEvents"]
        for e in parsed["traceEvents"]:
            assert e["ph"] in ("X", "M")
            if e["ph"] == "X":
                assert e["dur"] > 0 and "ts" in e
        xev = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
        chunk_ev = next(e for e in xev if e["name"] == "chunk")
        disp_ev = next(
            e for e in xev
            if e["name"] == "dispatch" and e["tid"] == chunk_ev["tid"]
        )
        assert disp_ev["ts"] <= chunk_ev["ts"]
        assert (
            chunk_ev["ts"] + chunk_ev["dur"]
            <= disp_ev["ts"] + disp_ev["dur"] + 0.01
        )

        # trace_report renders a per-stage breakdown from the dump
        report = _load_trace_report()
        rows = report.stage_table(doc["traces"])
        stages = {r["stage"] for r in rows}
        assert {"request", "dispatch", "supervise", "device", "chunk"} <= stages
        chunk_row = next(r for r in rows if r["stage"] == "chunk")
        assert chunk_row["device_ms"] > 0
        chrome_out = str(tmp_path / "report_chrome.json")
        rc = report.main([dump_path, "--top", "2", "--chrome", chrome_out])
        assert rc == 0
        out = capsys.readouterr().out
        assert "per-stage latency breakdown" in out
        assert "chunk" in out and "watchdog" in out
        json.load(open(chrome_out))


# ---------------------------------------------------------------------------
# trace_report unit tests (synthetic dump)


def _synthetic_dump():
    def span(name, span_id, parent, start, dur, **tags):
        return {
            "name": name, "span_id": span_id, "parent_id": parent,
            "trace_id": "t1", "start_us": start, "dur_us": dur,
            "tags": tags,
        }

    return {
        "reason": "watchdog",
        "wall_time": "2026-01-01T00:00:00Z",
        "traces": [
            {
                "trace_id": "t1", "root": "request", "dur_us": 900.0,
                "spans": [
                    span("request", "1", None, 0.0, 900.0, n_sigs=8),
                    span("dispatch", "2", "1", 100.0, 700.0,
                         reason="deadline"),
                    span("chunk", "3", "2", 150.0, 500.0,
                         device_wait_ns=400000, host_ns=50000),
                ],
            },
            {
                "trace_id": "t2", "root": "request", "dur_us": 300.0,
                "spans": [span("request", "1", None, 0.0, 300.0)],
            },
        ],
    }


class TestTraceReport:
    def test_stage_table_and_slowest(self):
        report = _load_trace_report()
        dump = _synthetic_dump()
        rows = report.stage_table(dump["traces"])
        by_stage = {r["stage"]: r for r in rows}
        assert by_stage["request"]["count"] == 2
        assert by_stage["request"]["max_us"] == 900.0
        assert by_stage["chunk"]["device_ms"] == 0.4
        assert by_stage["chunk"]["host_ms"] == 0.05
        top = report.slowest(dump["traces"], 1)
        assert len(top) == 1 and top[0]["trace_id"] == "t1"

    def test_load_traces_shapes(self, tmp_path):
        report = _load_trace_report()
        dump = _synthetic_dump()
        p = tmp_path / "dump.json"
        p.write_text(json.dumps(dump))
        meta, traces = report.load_traces(str(p))
        assert meta["reason"] == "watchdog"
        assert len(traces) == 2
        p2 = tmp_path / "bare.json"
        p2.write_text(json.dumps(dump["traces"]))
        meta2, traces2 = report.load_traces(str(p2))
        assert meta2 == {} and len(traces2) == 2
        p3 = tmp_path / "bad.json"
        p3.write_text('{"not": "traces"}')
        with pytest.raises(ValueError):
            report.load_traces(str(p3))

    def test_cli_main_renders_and_exports(self, tmp_path, capsys):
        report = _load_trace_report()
        p = tmp_path / "dump.json"
        p.write_text(json.dumps(_synthetic_dump()))
        out_chrome = tmp_path / "chrome.json"
        rc = report.main([str(p), "--top", "1", "--chrome", str(out_chrome)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "reason=watchdog" in out
        assert "chunk" in out
        doc = json.load(open(out_chrome))
        assert doc["traceEvents"]
        assert report.main([str(tmp_path / "missing.json")]) == 1
        assert "error:" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# /debug/traces HTTP routes


class TestDebugRoutes:
    def test_metrics_server_serves_traces_and_chrome(self):
        import urllib.request

        from cometbft_tpu.libs.metrics import MetricsServer

        reg = Registry()
        tr = tracelib.Tracer(sample=1.0, buffer=8)
        for i in range(3):
            root = tr.start_span("request", i=i)
            root.child("dispatch").end()
            root.end()
        srv = MetricsServer(reg, tracer=tr)
        port = srv.serve("127.0.0.1", 0)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces", timeout=5
            ) as r:
                doc = json.load(r)
            assert len(doc["traces"]) == 3
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces?n=1", timeout=5
            ) as r:
                assert len(json.load(r)["traces"]) == 1
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces/chrome", timeout=5
            ) as r:
                chrome = json.load(r)
            assert chrome["displayTimeUnit"] == "ms"
            assert any(e["ph"] == "X" for e in chrome["traceEvents"])
        finally:
            srv.stop()

    def test_metrics_server_without_tracer_has_no_debug_routes(self):
        import urllib.error
        import urllib.request

        from cometbft_tpu.libs.metrics import MetricsServer

        srv = MetricsServer(Registry())
        port = srv.serve("127.0.0.1", 0)
        try:
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/traces", timeout=5
                )
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# satellite regressions: min_batch threading without env mutation


class TestMinBatchThreading:
    def test_resident_routing_honors_spec_floor_without_env(self, monkeypatch):
        """The resident-commit eligibility and the add()/verify()
        verifier resolve the SAME floor from the BackendSpec — no
        re-read of CBFT_TPU_MIN_BATCH with a divergent default."""
        from cometbft_tpu.crypto import batch as cryptobatch

        monkeypatch.delenv("CBFT_TPU_MIN_BATCH", raising=False)
        lo = cryptobatch.BackendSpec(name="tpu", min_batch=5)
        hi = cryptobatch.BackendSpec(name="tpu", min_batch=50)
        assert cryptobatch.resident_commit_eligible(10, lo) is True
        assert cryptobatch.resident_commit_eligible(10, hi) is False
        # the add()/verify() path sees the identical floor
        assert cryptobatch.new_batch_verifier(lo)._floors["ed25519"] == 5
        assert cryptobatch.new_batch_verifier(hi)._floors["ed25519"] == 50
        # env still wins for operator A/B overrides, on BOTH paths
        monkeypatch.setenv("CBFT_TPU_MIN_BATCH", "7")
        assert cryptobatch.resident_commit_eligible(10, hi) is True
        assert cryptobatch.new_batch_verifier(hi)._floors["ed25519"] == 7

    def test_node_does_not_mutate_min_batch_env(self, monkeypatch):
        """Two in-process nodes with different [crypto] min_batch must
        not share the first node's floor through os.environ."""
        import tempfile

        from cometbft_tpu.cmd.commands import _load_config
        from cometbft_tpu.cmd.commands import main as cli_main
        from cometbft_tpu.node import default_new_node

        monkeypatch.delenv("CBFT_TPU_MIN_BATCH", raising=False)
        with tempfile.TemporaryDirectory() as d:
            cli_main(["--home", d, "init", "--chain-id", "env-iso"])
            cfg = _load_config(d)
            cfg.base.proxy_app = "kvstore"
            cfg.crypto.min_batch = 77
            node = default_new_node(cfg)
            try:
                assert "CBFT_TPU_MIN_BATCH" not in os.environ
                assert node.crypto_spec.min_batch == 77
                assert node.verify_scheduler.spec.min_batch == 77
                assert node.verify_supervisor.spec.min_batch == 77
            finally:
                for db in node._dbs:
                    db.close()


# ---------------------------------------------------------------------------
# node wiring


class TestNodeWiring:
    def test_node_builds_tracer_from_config(self, monkeypatch):
        import tempfile

        from cometbft_tpu.cmd.commands import _load_config
        from cometbft_tpu.cmd.commands import main as cli_main
        from cometbft_tpu.node import default_new_node

        monkeypatch.delenv("CBFT_TRACE_SAMPLE", raising=False)
        monkeypatch.delenv("CBFT_TRACE_BUFFER", raising=False)
        with tempfile.TemporaryDirectory() as d:
            cli_main(["--home", d, "init", "--chain-id", "trace-node"])
            cfg = _load_config(d)
            cfg.base.proxy_app = "kvstore"
            cfg.instrumentation.trace_sample = 0.5
            cfg.instrumentation.trace_buffer = 17
            node = default_new_node(cfg)
            try:
                assert node.tracer.sample == 0.5
                assert node.tracer.buffer_size == 17
                assert node.tracer._dump_dir == os.path.join(d, "data")
                # the scheduler and supervisor share the node's tracer
                assert node.verify_scheduler._tracer is node.tracer
                assert node.verify_supervisor._tracer is node.tracer
            finally:
                for db in node._dbs:
                    db.close()
