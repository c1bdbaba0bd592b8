"""Wire-ledger contract tests (crypto/wire.py + the mesh dispatch
instrumentation, scheduler demux feed, calibration cold seed, and the
verify_top / trace_report render surfaces).

The load-bearing acceptance bounds:

* a live dispatch's per-phase sums reconcile with its wall time within
  10% (coverage in [0.9, 1.1]) on a payload large enough that the
  measured phases dominate loop bookkeeping;
* ``CostProfile.predict_ms(route, bucket)`` lands within 2x of a
  subsequently measured dispatch once the profile holds >= 5
  observations (compile-warm; a cold first dispatch would fold the JIT
  wall into the EWMA and wreck the prediction — by design: the ledger
  reports what the wire actually did);
* the chaos rung (faults.run_chaos_wire) attributes an injected slow
  link to the h2d phase, not compute — the ledger's whole point;
* ``verify_wire_*`` conformance lives in test_metrics.py (one strict
  family check per metric plane).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cometbft_tpu.config import Config
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.faults import run_chaos_wire
from cometbft_tpu.crypto.scheduler import VerifyScheduler
from cometbft_tpu.crypto.telemetry import TelemetryHub
from cometbft_tpu.crypto.tpu import calibrate
from cometbft_tpu.crypto.tpu import mesh
from cometbft_tpu.crypto.wire import (
    CHUNK_PHASES,
    CostProfile,
    WireLedger,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _note_uniform_chunk(ledger, route="single", device="dev0",
                        bucket=256, lanes=200, wire_bytes=32_768,
                        pack_s=1e-4, h2d_s=2e-3, compute_s=5e-4,
                        d2h_s=1e-4, hidden_s=0.0):
    ledger.note_chunk(route, device, bucket, lanes, wire_bytes,
                      pack_s, h2d_s, compute_s, d2h_s, hidden_s=hidden_s)


# ---------------------------------------------------------------------------
# ledger unit behavior
# ---------------------------------------------------------------------------


class TestWireLedgerUnit:
    def test_profile_folds_and_snapshot_shape(self):
        ledger = WireLedger(window=8)
        for _ in range(4):
            _note_uniform_chunk(ledger, hidden_s=1e-3)
        snap = ledger.snapshot()
        assert snap["window"] == 8
        assert snap["chunks"] == 4 and snap["dispatches"] == 0
        (row,) = snap["profiles"]
        assert (row["route"], row["bucket"], row["device"]) == \
            ("single", 256, "dev0")
        assert row["n"] == 4
        for ph in CHUNK_PHASES:
            ent = row["phases_ms"][ph]
            assert set(ent) == {"ewma", "p50", "p99"}
        # identical samples: ewma == p50 == p99
        assert row["phases_ms"]["h2d"]["p50"] == pytest.approx(2.0)
        assert row["phases_ms"]["h2d"]["ewma"] == pytest.approx(2.0)
        assert row["bytes_per_lane"] == pytest.approx(32_768 / 200, rel=0.01)
        # 1ms hidden of 2ms transfer per chunk
        assert row["overlap"] == pytest.approx(0.5)
        # effective bandwidth = bytes / h2d
        assert row["effective_MBps"] == pytest.approx(
            32_768 / 2e-3 / 1e6, rel=0.01
        )

    def test_overlap_clamped_to_transfer_time(self):
        # hidden can never exceed h2d (a clock-skew guard)
        ledger = WireLedger(window=4)
        _note_uniform_chunk(ledger, h2d_s=1e-3, hidden_s=5e-3)
        (row,) = ledger.snapshot()["profiles"]
        assert row["overlap"] == pytest.approx(1.0)

    def test_dispatch_record_reconciliation_fields(self):
        ledger = WireLedger(window=4)
        ledger.note_dispatch(
            "single", "dev0", n=512, wall_s=4e-3,
            pack_s=1e-3, h2d_s=1e-3, compute_s=1.5e-3, d2h_s=5e-4,
            hidden_s=5e-4, wire_bytes=65_536, chunks=2,
        )
        snap = ledger.snapshot()
        assert snap["dispatches"] == 1
        (rec,) = snap["recent"]
        assert rec["wall_ms"] == pytest.approx(4.0)
        assert rec["coverage"] == pytest.approx(1.0)   # phases sum to wall
        assert rec["overlap"] == pytest.approx(0.5)    # half the h2d hidden
        assert rec["bytes"] == 65_536 and rec["chunks"] == 2

    def test_demux_pow2_bucketing(self):
        ledger = WireLedger(window=4)
        ledger.note_demux("cpu", 200, 5e-5)   # 200 sigs -> bucket 256
        ledger.note_demux("cpu", 250, 7e-5)
        ledger.note_demux("single", 8, 1e-5)
        snap = ledger.snapshot()
        assert snap["demux_notes"] == 3
        by_key = {(d["route"], d["bucket"]): d for d in snap["demux"]}
        assert by_key[("cpu", 256)]["n"] == 2
        assert by_key[("single", 8)]["n"] == 1
        assert by_key[("cpu", 256)]["p50_ms"] > 0

    def test_default_ledger_install_and_restore(self):
        ledger = WireLedger(window=4)
        prev = wirelib.set_default_ledger(ledger)
        try:
            assert wirelib.default_ledger() is ledger
            assert wirelib.set_default_ledger(None) is ledger
            assert wirelib.default_ledger() is None
        finally:
            wirelib.set_default_ledger(prev)

    def test_env_knobs_win_over_config(self, monkeypatch):
        monkeypatch.delenv("CBFT_WIRE_LEDGER", raising=False)
        monkeypatch.delenv("CBFT_WIRE_WINDOW", raising=False)
        assert wirelib.wire_ledger_default(True) is True
        assert wirelib.wire_ledger_default(False) is False
        monkeypatch.setenv("CBFT_WIRE_LEDGER", "0")
        assert wirelib.wire_ledger_default(True) is False
        monkeypatch.setenv("CBFT_WIRE_LEDGER", "on")
        assert wirelib.wire_ledger_default(False) is True
        assert wirelib.wire_window_default(32) == 32
        monkeypatch.setenv("CBFT_WIRE_WINDOW", "16")
        assert wirelib.wire_window_default(32) == 16
        monkeypatch.setenv("CBFT_WIRE_WINDOW", "garbage")
        assert wirelib.wire_window_default(32) == 32

    def test_config_validates_wire_knobs(self):
        cfg = Config()
        cfg.validate_basic()
        cfg.instrumentation.wire_window = 0
        with pytest.raises(ValueError):
            cfg.validate_basic()
        cfg.instrumentation.wire_window = 64
        cfg.instrumentation.wire_ledger = "yes"
        with pytest.raises(ValueError):
            cfg.validate_basic()


# ---------------------------------------------------------------------------
# cost queries
# ---------------------------------------------------------------------------


class TestCostProfile:
    def test_empty_ledger_predicts_nothing(self):
        assert WireLedger().predict_ms("single", 256) is None

    def test_cold_seed_from_link_probe(self):
        ledger = WireLedger(window=4)
        ledger.seed_link({
            "platform": "cpu", "kernel_roundtrip_ms": 0.05,
            "effective_MBps": 1000.0, "fixed_latency_ms_est": 0.95,
        })
        pred = ledger.predict_ms("single", 1024)
        # fixed (0.95 + 0.05) + 1024 lanes * 128 B/lane / 1 GB/s
        assert pred == pytest.approx(1.0 + 1024 * 128.0 / 1e9 * 1e3,
                                     rel=0.01)
        # bigger buckets cost strictly more on the same curve
        assert ledger.predict_ms("single", 8192) > pred

    def test_warm_profile_beats_cold_seed(self):
        ledger = WireLedger(window=8)
        ledger.seed_link({"effective_MBps": 1.0,
                          "fixed_latency_ms_est": 500.0})
        for _ in range(6):
            _note_uniform_chunk(ledger, bucket=256)
        # exact-bucket hit: per-chunk phase sum, not the silly cold seed
        pred = ledger.predict_ms("single", 256)
        assert pred == pytest.approx((1e-4 + 2e-3 + 5e-4 + 1e-4) * 1e3,
                                     rel=0.05)
        assert ledger.observations("single", 256) == 6

    def test_nearest_bucket_scales_the_variable_part(self):
        ledger = WireLedger(window=8)
        ledger.seed_link({"fixed_latency_ms_est": 1.0})
        for _ in range(5):
            _note_uniform_chunk(ledger, bucket=1024, h2d_s=4e-3)
        per_chunk = ledger.predict_ms("single", 1024)
        smaller = ledger.predict_ms("single", 256)
        assert smaller is not None and smaller < per_chunk
        # scaled-down lanes keep the fixed latency floor
        assert smaller >= 1.0
        # above the largest measured bucket: split into chunks
        bigger = ledger.predict_ms("single", 4096)
        assert bigger > per_chunk

    def test_cost_profile_wrapper(self):
        ledger = WireLedger(window=4)
        for _ in range(3):
            _note_uniform_chunk(ledger)
        cp = ledger.cost_profile()
        assert isinstance(cp, CostProfile)
        assert cp.predict_ms("single", 256) == \
            ledger.predict_ms("single", 256)
        assert cp.observations("single", 256) == 3


class TestPredictMsEdges:
    """Pinned edge behavior (PR 15): the decision plane prices every
    candidate on every flush through predict_ms, so it must NEVER
    raise and its edges are regression-locked here."""

    def test_unknown_route_falls_to_seed_then_none(self):
        ledger = WireLedger(window=4)
        for _ in range(3):
            _note_uniform_chunk(ledger, route="single")
        # no profile for the route, no link seed: None (not a raise)
        assert ledger.predict_ms("no-such-route", 256) is None
        # with a link seed the unknown route prices off the cold curve
        ledger.seed_link({"effective_MBps": 1000.0,
                          "fixed_latency_ms_est": 1.0})
        pred = ledger.predict_ms("no-such-route", 256)
        assert pred is not None and pred > 0.0

    def test_bucket_below_smallest_observed_keeps_fixed_floor(self):
        ledger = WireLedger(window=8)
        ledger.seed_link({"fixed_latency_ms_est": 1.0})
        for _ in range(5):
            _note_uniform_chunk(ledger, bucket=1024, h2d_s=4e-3)
        per_chunk = ledger.predict_ms("single", 1024)
        tiny = ledger.predict_ms("single", 1)
        # only the size-dependent part scales down: never below the
        # link's fixed latency, never negative
        assert tiny is not None and 1.0 <= tiny <= per_chunk

    def test_bucket_above_largest_never_cheaper_than_one_chunk(self):
        ledger = WireLedger(window=8)
        # pathological overlap: hidden transfer bigger than the chunk
        # itself must not predict a megabatch cheaper than one chunk
        for _ in range(5):
            _note_uniform_chunk(ledger, bucket=256, h2d_s=50e-3,
                                hidden_s=50e-3)
        per_chunk = ledger.predict_ms("single", 256)
        mega = ledger.predict_ms("single", 16384)
        assert mega >= per_chunk

    def test_malformed_bucket_answers_none_never_raises(self):
        ledger = WireLedger(window=4)
        for _ in range(3):
            _note_uniform_chunk(ledger)
        for bad in (None, "256x", object()):
            assert ledger.predict_ms("single", bad) is None
        # and through the CostProfile wrapper the decision plane holds
        assert ledger.cost_profile().predict_ms("single", None) is None

    def test_cold_ledger_every_route_is_none(self):
        ledger = WireLedger()
        for route in ("cpu", "single", "sharded", "indexed"):
            assert ledger.predict_ms(route, 64) is None


# ---------------------------------------------------------------------------
# calibration cold seed (tools/tpu_link_probe.py --merge roundtrip)
# ---------------------------------------------------------------------------


class TestCalibrationSeed:
    PROBE = {
        "platform": "cpu", "kernel_roundtrip_ms": 0.05,
        "put_64KiB_ms": 0.06, "effective_MBps": 6185.6,
        "fixed_latency_ms_est": 0.98, "junk": "ignore-me",
    }

    def test_merge_and_seed_roundtrip(self, tmp_path):
        calibrate.set_table_path(str(tmp_path / "calib.json"))
        try:
            table = calibrate.merge_link_profile(self.PROBE)
            assert table is not None
            link = calibrate.load_link_profile()
            assert link["effective_MBps"] == pytest.approx(6185.6)
            assert link["put_64KiB_ms"] == pytest.approx(0.06)
            assert link["platform"] == "cpu"
            assert "junk" not in link
            assert link["measured_at"] > 0
            ledger = WireLedger(window=4)
            assert wirelib.seed_from_calibration(ledger) is True
            assert ledger.link()["effective_MBps"] == pytest.approx(6185.6)
            assert ledger.predict_ms("single", 1024) is not None
        finally:
            calibrate.set_table_path(None)

    def test_merge_rejects_unusable_probe(self, tmp_path):
        calibrate.set_table_path(str(tmp_path / "calib.json"))
        try:
            assert calibrate.merge_link_profile({"platform": "cpu"}) is None
            assert calibrate.load_link_profile() == {}
            ledger = WireLedger(window=4)
            assert wirelib.seed_from_calibration(ledger) is False
        finally:
            calibrate.set_table_path(None)

    def test_probe_cli_merges(self, tmp_path):
        path = tmp_path / "calib.json"
        res = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "tools", "tpu_link_probe.py"),
             "--merge", "--calibration", str(path)],
            capture_output=True, text=True, timeout=300, cwd=_REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert res.returncode == 0, res.stderr[-400:]
        # the last stdout line is still the full probe document
        doc = json.loads(res.stdout.strip().splitlines()[-1])
        # effective_MBps is omitted when a loaded host inverts the
        # size/latency slope; the fixed-latency estimate always lands
        assert "fixed_latency_ms_est" in doc
        table = json.loads(path.read_text())
        link = table["link"]
        assert link["fixed_latency_ms_est"] == pytest.approx(
            doc["fixed_latency_ms_est"], abs=0.01
        )
        if "effective_MBps" in doc:
            assert link["effective_MBps"] == pytest.approx(
                doc["effective_MBps"], rel=0.01
            )


# ---------------------------------------------------------------------------
# live mesh dispatch: the acceptance bounds
# ---------------------------------------------------------------------------


def _parity_kernel():
    import jax

    @jax.jit
    def parity(rows):
        return (rows.sum(axis=0) % 2) == 0

    return parity


class TestMeshDispatchAttribution:
    """dispatch_batch feeds the ledger per chunk; the payload here is
    sized so measured phases dominate the chunk loop's bookkeeping
    (tiny payloads legitimately report low coverage — the wall is all
    Python, not wire)."""

    def test_phase_sums_reconcile_and_overlap_reported(self):
        kernel = _parity_kernel()
        rng = np.random.default_rng(7)
        full = rng.integers(0, 100, size=(256, 4096)).astype(np.int32)
        want = (full.sum(axis=0) % 2) == 0
        prev = wirelib.set_default_ledger(None)
        try:
            with mesh.route_scope(mesh.ROUTE_SINGLE):
                # compile-warm with no ledger: the JIT wall is not wire
                mesh.dispatch_batch(kernel, [full], 4096, 1024, 8)
                ledger = WireLedger(window=8)
                wirelib.set_default_ledger(ledger)
                for _ in range(5):
                    out = mesh.dispatch_batch(kernel, [full], 4096, 1024, 8)
        finally:
            wirelib.set_default_ledger(prev)
        assert (out == want).all()
        snap = ledger.snapshot()
        assert snap["dispatches"] == 5
        assert snap["chunks"] == 20  # 4 chunks of 1024 per dispatch
        covs = [r["coverage"] for r in snap["recent"]]
        # the phases are disjoint stretches of the dispatch's own wall:
        # their sum never exceeds it. How much of the wall they cover is
        # a time, and a CPU shared by the suite's workers gives no time.
        assert all(0 < c <= 1.0 for c in covs), covs
        (row,) = snap["profiles"]
        assert (row["route"], row["bucket"]) == ("single", 1024)
        # the double-buffered pipeline hid SOME transfer on chunks 2..4
        assert row["overlap"] is not None and row["overlap"] > 0
        assert row["effective_MBps"] is not None
        assert row["predicted_ms"] is not None

    def test_predict_within_2x_of_measured_after_5_observations(self):
        """The prediction is the profile's own phases, never outside what
        the ledger measured of the five dispatches (each is one chunk):
        no second clock is in the verdict, so a busy CPU cannot move it."""
        kernel = _parity_kernel()
        rng = np.random.default_rng(11)
        single = rng.integers(0, 100, size=(256, 1024)).astype(np.int32)
        prev = wirelib.set_default_ledger(None)
        try:
            with mesh.route_scope(mesh.ROUTE_SINGLE):
                mesh.dispatch_batch(kernel, [single], 1024, 1024, 8)
                ledger = WireLedger(window=8)
                wirelib.set_default_ledger(ledger)
                for _ in range(5):
                    mesh.dispatch_batch(kernel, [single], 1024, 1024, 8)
        finally:
            wirelib.set_default_ledger(prev)
        assert ledger.observations("single", 1024) == 5
        pred = ledger.predict_ms("single", 1024)
        assert pred is not None and pred > 0
        recent = ledger.snapshot()["recent"]
        assert [r["chunks"] for r in recent] == [1] * 5
        measured = [
            [r[ph + "_ms"] for r in recent] for ph in CHUNK_PHASES
        ]
        slack = 0.001 * len(CHUNK_PHASES)  # the records round to 1 us
        assert (sum(min(ph) for ph in measured) - slack <= pred
                <= sum(max(ph) for ph in measured) + slack), (pred, measured)

    def test_uninstalled_ledger_costs_nothing(self):
        # the mesh loop must run identically with no ledger installed
        kernel = _parity_kernel()
        ones = np.ones((2, 17), np.int32)
        prev = wirelib.set_default_ledger(None)
        try:
            with mesh.route_scope(mesh.ROUTE_SINGLE):
                out = mesh.dispatch_batch(kernel, [ones], 17, 16, 8)
        finally:
            wirelib.set_default_ledger(prev)
        assert out.shape == (17,) and out.all()


class TestChaosWireRung:
    def test_jittery_link_attributed_to_transfer(self):
        summary = run_chaos_wire(seed=7, jitter_ms=20.0)
        assert summary["ok"] is True
        assert summary["injected_jitter_ms"] > 0
        assert summary["h2d_delta_ms"] >= 0.5 * summary["injected_jitter_ms"]
        assert summary["compute_delta_ms"] <= max(
            5.0, 0.25 * summary["injected_jitter_ms"]
        )


# ---------------------------------------------------------------------------
# scheduler demux feed + telemetry hub source
# ---------------------------------------------------------------------------


def _make_items(n, tag=b"wire"):
    items = []
    for i in range(n):
        k = ed.gen_priv_key_from_secret(tag + bytes([i & 0xFF, i >> 8]))
        msg = b"wire-msg-" + i.to_bytes(4, "big")
        items.append((k.pub_key(), msg, k.sign(msg)))
    return items


class TestSchedulerDemuxFeed:
    def test_flush_notes_demux_phase(self):
        ledger = WireLedger(window=8)
        prev = wirelib.set_default_ledger(ledger)
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=500)
        sched.start()
        try:
            ok, mask = sched.submit(
                _make_items(4), subsystem="blocksync", height=9
            ).result(timeout=60)
        finally:
            sched.stop()
            wirelib.set_default_ledger(prev)
        assert ok and all(mask)
        snap = ledger.snapshot()
        assert snap["demux_notes"] >= 1
        assert any(d["route"] == "cpu" for d in snap["demux"])

    def test_hub_source_lands_in_debug_verify(self):
        hub = TelemetryHub()
        hub.note_request(4, 0.0, 0.001, True, subsystem="light")
        ledger = WireLedger(window=8)
        _note_uniform_chunk(ledger, hidden_s=1e-3)
        ledger.note_demux("cpu", 4, 1e-5)
        hub.register_source("wire", ledger.snapshot)
        wire = hub.snapshot()["sources"]["wire"]
        assert wire["chunks"] == 1 and wire["demux_notes"] == 1
        assert wire["profiles"][0]["bucket"] == 256


# ---------------------------------------------------------------------------
# render surfaces: verify_top wire table, trace_report --wire
# ---------------------------------------------------------------------------


class TestVerifyTopWireTable:
    def test_once_renders_wire_section(self, tmp_path):
        hub = TelemetryHub()
        hub.note_request(4, 0.0, 0.001, True, subsystem="light")
        ledger = WireLedger(window=8)
        ledger.seed_link({"platform": "cpu", "effective_MBps": 6185.6,
                          "fixed_latency_ms_est": 0.98,
                          "kernel_roundtrip_ms": 0.05})
        for _ in range(3):
            _note_uniform_chunk(ledger, hidden_s=1e-3)
        ledger.note_demux("cpu", 200, 5e-5)
        hub.register_source("wire", ledger.snapshot)
        path = tmp_path / "snap.json"
        path.write_text(json.dumps(hub.snapshot()))
        res = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "verify_top.py"),
             str(path), "--once"],
            capture_output=True, text=True, timeout=60, cwd=_REPO,
        )
        assert res.returncode == 0, res.stderr[-400:]
        out = res.stdout
        assert "wire ledger" in out
        assert "overlap" in out and "pred_ms" in out
        assert "50.0%" in out          # 1ms hidden of 2ms h2d
        assert "link ceiling" in out and "6185.6" in out
        assert "demux" in out and "cpu/256" in out
        # the phase bar renders with the h2d glyph dominant
        assert "hh" in out


class TestTraceReportWire:
    @staticmethod
    def _load():
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "trace_report_wire_test",
            os.path.join(_REPO, "tools", "trace_report.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    @staticmethod
    def _chunk_span(span_id, pack_ns, h2d_ns, compute_ns, wait_ns,
                    hidden_ns, pad=1024):
        return {
            "name": "chunk", "span_id": span_id, "parent_id": "1",
            "trace_id": "t1", "start_us": 0.0,
            "dur_us": (pack_ns + h2d_ns + compute_ns + wait_ns) / 1e3,
            "tags": {
                "pad": pad, "pack_ns": pack_ns, "h2d_ns": h2d_ns,
                "compute_ns": compute_ns, "device_wait_ns": wait_ns,
                "hidden_ns": hidden_ns, "host_ns": pack_ns,
            },
        }

    def _dump(self):
        return [{
            "trace_id": "t1", "root": "request", "dur_us": 9000.0,
            "spans": [
                {"name": "request", "span_id": "1", "parent_id": None,
                 "trace_id": "t1", "start_us": 0.0, "dur_us": 9000.0,
                 "tags": {}},
                self._chunk_span("2", 100_000, 2_000_000, 500_000,
                                 100_000, 0),
                self._chunk_span("3", 100_000, 2_000_000, 500_000,
                                 100_000, 1_000_000),
            ],
        }]

    def test_wire_table_per_bucket(self):
        report = self._load()
        rows = report.wire_table(self._dump())
        (row,) = rows
        assert (row["stage"], row["bucket"], row["chunks"]) == \
            ("chunk", 1024, 2)
        assert row["h2d_p50_ms"] == pytest.approx(2.0)
        assert row["pack_p50_ms"] == pytest.approx(0.1)
        # 1ms hidden of 4ms total transfer across the bucket
        assert row["overlap"] == "25.0%"

    def test_stage_table_gains_wire_columns(self):
        report = self._load()
        rows = report.stage_table(self._dump())
        chunk = {r["stage"]: r for r in rows}["chunk"]
        assert chunk["pack_ms"] == pytest.approx(0.2)
        assert chunk["h2d_ms"] == pytest.approx(4.0)
        assert chunk["compute_ms"] == pytest.approx(1.0)
        assert chunk["hidden_ms"] == pytest.approx(1.0)
        # spans without wire tags don't grow the columns
        req = {r["stage"]: r for r in rows}["request"]
        assert "pack_ms" not in req

    def test_render_wire_flag(self):
        report = self._load()
        out = report.render({}, self._dump(), wire=True)
        assert "wire phases per bucket" in out
        assert "25.0%" in out
        out_plain = report.render({}, self._dump())
        assert "wire phases per bucket" not in out_plain


# ---------------------------------------------------------------------------
# bench history: transfer/prepare regressions must read lower-is-better
# ---------------------------------------------------------------------------


class TestBenchHistoryDirection:
    @staticmethod
    def _load():
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_history_wire_test",
            os.path.join(_REPO, "tools", "bench_history.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_wire_phase_leaves_are_lower_is_better(self):
        bh = self._load()
        for leaf in ("h2d_transfer_ms", "result_transfer_ms",
                     "host_prepare_ms", "tpu.breakdown.h2d_transfer_ms"):
            assert bh.direction(leaf) == bh.LOWER_IS_BETTER, leaf
        # throughput leaves keep their direction
        assert bh.direction("sigs_per_sec") == bh.HIGHER_IS_BETTER
