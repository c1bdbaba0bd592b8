"""Device-memory plane, incident profiler, and regression sentinel.

Covers PR 9's observability plane end to end on the virtual CPU mesh:

* the calibrated footprint model (static Straus seed, EWMA correction,
  calibration-table round trip);
* the pre-dispatch memory guard demoting the reactive OOM rung: under a
  CBFT_FAULT_OOM_RATE/CBFT_FAULT_OOM_ABOVE allocator-model injection the
  guard shrinks the chunk cap BEFORE dispatch, so zero
  RESOURCE_EXHAUSTED ever reaches the supervisor's breaker
  (crypto/faults.py run_chaos_memory_guard — the same proof
  tools/chaos.py --memory-guard runs);
* model-only degradation on stats-less backends;
* ProfilerCapture gating, retention, and the /debug/profile endpoint
  (the real jax.profiler capture is `slow`-marked);
* the tools/bench_history.py sentinel: self-test (synthetic 20%
  regression must flag, clean and single-blip ledgers must pass) and
  the --append stage-record writer bench.py uses.
"""

import json
import os
import subprocess
import sys

import pytest

from cometbft_tpu.crypto import faults as faultlib
from cometbft_tpu.crypto.tpu import calibrate as caliblib
from cometbft_tpu.crypto.tpu import memory as memlib
from cometbft_tpu.crypto.tpu import topology as topolib
from cometbft_tpu.libs import profiling as proflib
from cometbft_tpu.libs.metrics import MetricsServer, Registry

_TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
)


@pytest.fixture
def handle():
    """Fault-domain 0's device handle, guard/shrink state restored."""
    h = topolib.default_topology().device(0)
    h.reset_chunk_shrink()
    yield h
    h.reset_chunk_shrink()


class TestFootprintModel:
    def test_static_seed_matches_straus_estimate(self):
        plane = memlib.MemoryPlane(stats=False)
        # ~70 MB per 16384-lane Straus chunk (ed25519_batch.py)
        assert plane.bytes_per_lane("ed25519", 16384) == pytest.approx(
            memlib.SEED_BYTES_PER_LANE
        )
        assert memlib.SEED_BYTES_PER_LANE * 16384 == pytest.approx(
            70 * 1024 * 1024, rel=0.2
        )

    def test_projection_scales_with_bucket(self):
        plane = memlib.MemoryPlane(stats=False)
        small = plane.projected_bytes("ed25519", 1024)
        big = plane.projected_bytes("ed25519", 8192)
        assert big == pytest.approx(small * 8, rel=0.01)

    def test_ewma_correction_and_export(self):
        plane = memlib.MemoryPlane(stats=False)
        assert plane.export_footprints() == {}  # seed-only: nothing learned
        plane.observe_footprint("ed25519", 1024, 1024 * 9000)
        assert plane.bytes_per_lane("ed25519", 1024) == pytest.approx(9000.0)
        # EWMA folds the next observation toward the new peak
        plane.observe_footprint("ed25519", 1024, 1024 * 5000)
        bpl = plane.bytes_per_lane("ed25519", 1024)
        assert 5000.0 < bpl < 9000.0
        exported = plane.export_footprints()
        assert exported["ed25519"][1024] == pytest.approx(bpl)

    def test_nonpositive_observations_ignored(self):
        plane = memlib.MemoryPlane(stats=False)
        plane.observe_footprint("ed25519", 1024, 0)
        plane.observe_footprint("ed25519", 0, 4096)
        assert plane.export_footprints() == {}

    def test_calibration_round_trip(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "CBFT_TPU_CALIBRATION", str(tmp_path / "calib.json")
        )
        plane = memlib.MemoryPlane(stats=False)
        plane.observe_footprint("ed25519", 2048, 2048 * 7777)
        assert caliblib.merge_memory_footprints(
            plane.export_footprints()
        ) is not None
        loaded = caliblib.load_memory_footprints()
        assert loaded["ed25519"][2048] == pytest.approx(7777.0, abs=0.1)
        # a fresh plane seeds its model from the persisted table
        warm = memlib.MemoryPlane(stats=False)
        assert warm.bytes_per_lane("ed25519", 2048) == pytest.approx(
            7777.0, abs=0.1
        )


class TestModelOnlyDegradation:
    def test_stats_less_backend_reports_model_mode(self, handle):
        plane = memlib.MemoryPlane(
            stats=False, model_limit_bytes=1 << 30, headroom_fraction=0.5
        )
        doc = plane.device_view(handle)
        assert doc["mode"] == "model"
        assert doc["bytes_in_use"] == 0
        assert plane.free_headroom_bytes(handle) == (1 << 30) // 2

    def test_env_limit_drives_model(self, monkeypatch):
        monkeypatch.setenv("CBFT_MEM_LIMIT_BYTES", str(1 << 20))
        assert memlib.model_limit_bytes_default() == 1 << 20

    def test_snapshot_shape(self, handle):
        plane = memlib.MemoryPlane(stats=False)
        snap = plane.snapshot()
        assert snap["seed_bytes_per_lane"] > 0
        doc = snap["devices"][handle.label]
        assert {"mode", "bytes_in_use", "headroom_bytes", "guard_cap"} \
            <= set(doc)


class TestPreDispatchGuard:
    def test_guard_shrinks_cap_to_fit_headroom(self, handle):
        # headroom fits ~256 lanes × pipeline depth: the guard must
        # halve 8192 down until the projection fits, and clamp the
        # handle so every cap consumer (mesh dispatch) sees it
        from cometbft_tpu.crypto.tpu import mesh

        try:
            depth = mesh.pipeline_depth()
        except ValueError:
            depth = 2
        limit = int(memlib.SEED_BYTES_PER_LANE * 256 * depth / 0.9) + 1
        plane = memlib.MemoryPlane(
            stats=False, model_limit_bytes=limit, poll_ms=0
        )
        cap = plane.refresh_guard(handle, 8192, 64)
        assert cap <= 256
        assert handle.memory_guard_cap() == cap
        assert handle.chunk_cap(8192, 64) == cap
        # labeled counters accumulate in with_labels() children — sum
        # the series for the total
        shrinks = sum(
            c.value() for c in plane.metrics.guard_shrinks._series()
        )
        assert shrinks >= 5  # 8192 -> 256 is five halvings

    def test_guard_releases_when_headroom_returns(self, handle):
        plane = memlib.MemoryPlane(
            stats=False, model_limit_bytes=1 << 40, poll_ms=0
        )
        cap = plane.refresh_guard(handle, 8192, 64)
        assert cap == handle.chunk_cap(8192, 64)
        assert handle.memory_guard_cap() is None

    def test_guard_floors_at_min_pad(self, handle):
        plane = memlib.MemoryPlane(
            stats=False, model_limit_bytes=1, poll_ms=0
        )
        # nothing fits: the guard floors at min_pad and the reactive
        # rung stays the backstop instead of wedging dispatch at 0
        assert plane.refresh_guard(handle, 8192, 64) == 64


    @pytest.mark.parametrize("fits,want_guard,want_cap", [
        # the launch fits: no guard, though the 8,192 cap would not
        (2048, None, 2048),
        # it does not: the guard halves from the launch, not from the cap
        (512, 512, 512),
    ])
    def test_guard_projects_the_launch_not_the_cap(
            self, handle, fits, want_guard, want_cap):
        from cometbft_tpu.crypto.tpu import mesh

        limit = int(
            memlib.SEED_BYTES_PER_LANE * fits * mesh.pipeline_depth() / 0.9
        ) + 1
        plane = memlib.MemoryPlane(
            stats=False, model_limit_bytes=limit, poll_ms=0
        )
        assert plane.refresh_guard(handle, 8192, 64, launch=2048) == want_cap
        assert handle.memory_guard_cap() == want_guard
        shrinks = sum(
            c.value() for c in plane.metrics.guard_shrinks._series()
        )
        assert shrinks == (0 if want_guard is None else 2)  # 2048 -> 512

    def test_dispatch_tells_its_observers_the_largest_launch(
            self, handle, monkeypatch):
        """dispatch_batch with a launch size: the guard is asked about
        that launch and the footprint model is corrected at the largest
        launch issued, not at the bucket of the whole batch."""
        import numpy as np

        from cometbft_tpu.crypto.tpu import mesh

        class Plane:
            asked, observed = [], []

            def refresh_guard(self, dev, cap, min_pad, kernel, launch=None):
                self.asked.append((cap, launch))

            def device_view(self, dev):
                return {}

            def observe_dispatch(self, dev, kernel, lanes, baseline_in_use):
                self.observed.append(lanes)

        plane = Plane()
        monkeypatch.setattr(memlib, "default_plane", lambda: plane)
        monkeypatch.setattr(
            mesh, "launch_stream",
            lambda kernel, launches, build, n, **kw: (
                np.zeros(n, bool), {"chunks": 0}))
        monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
        mesh.configure_chunk_cap(None)
        with mesh.route_scope(mesh.ROUTE_SINGLE):
            mesh.dispatch_batch(
                len, [np.zeros(6464)], 6464, 8192, 64, device=handle,
                launch=2048)
            mesh.dispatch_batch(
                len, [np.zeros(6464)], 6464, 8192, 64, device=handle)
        assert plane.asked == [(8192, 2048), (8192, None)]
        assert plane.observed == [2048, 8192]


class TestGuardPreemptsInjectedOom:
    def test_chaos_memory_guard(self):
        """The PR's headline invariant, via the same harness
        tools/chaos.py --memory-guard runs: with the allocator-model
        OOM injection armed (oom_rate=1.0, oom_above_lanes=256), the
        reactive rung pays one real RESOURCE_EXHAUSTED per halving,
        then the guard-on phase dispatches the identical workload with
        ZERO OOMs fired and zero reactive shrinks."""
        summary = faultlib.run_chaos_memory_guard(seed=11, inner="cpu")
        assert summary["wrong_verdicts"] == 0
        assert summary["reactive_ooms"] > 0
        assert summary["reactive_shrinks"] > 0
        assert summary["guard_cap"] <= 256
        assert summary["guarded_ooms"] == 0
        assert summary["guarded_shrinks"] == 0
        assert summary["guard_shrink_events"] > 0
        assert summary["state_final"] == "healthy"

    def test_fault_plan_env_round_trip(self, monkeypatch):
        monkeypatch.setenv("CBFT_FAULT_OOM_RATE", "1.0")
        monkeypatch.setenv("CBFT_FAULT_OOM_ABOVE", "128")
        plan = faultlib.FaultPlan.from_env()
        assert plan.oom_rate == 1.0
        assert plan.oom_above_lanes == 128

    def test_allocator_model_respects_guarded_cap(self, handle):
        """An injected OOM (rate 1.0) must NOT fire once the guard has
        clamped the cap to the allocator threshold — the workload fits
        in modeled HBM, so the fault's own model agrees it fits."""
        from cometbft_tpu.crypto import batch as cryptobatch
        import cometbft_tpu.crypto.ed25519 as ed

        plan = faultlib.FaultPlan(
            seed=3, oom_rate=1.0, oom_above_lanes=256
        )
        key = ed.gen_priv_key_from_secret(b"memory-guard-test")
        pk = key.pub_key()
        msg = b"guarded dispatch"
        sig = key.sign(msg)

        def dispatch():
            bv = faultlib.FaultyBackend(
                plan, cryptobatch.new_batch_verifier("cpu")
            )
            bv.add(pk, msg, sig)
            return bv.verify()

        with pytest.raises(Exception):
            dispatch()  # unguarded cap 8192 > 256: the fault fires
        assert plan.ooms_fired == 1
        handle.set_memory_guard_cap(256)
        ok, mask = dispatch()  # fits in modeled HBM: never fires
        assert ok and mask == [True]
        assert plan.ooms_fired == 1


class TestProfilerCapture:
    def test_unavailable_without_profile_dir(self):
        prof = proflib.ProfilerCapture(profile_dir=None)
        assert not prof.available()
        assert prof.capture(duration_ms=10) is None

    def test_burn_gating(self, tmp_path):
        prof = proflib.ProfilerCapture(
            profile_dir=str(tmp_path), on_burn_threshold=0.0
        )
        assert not prof.on_burn(99.0)  # threshold 0 = disabled
        armed = proflib.ProfilerCapture(
            profile_dir=str(tmp_path), on_burn_threshold=2.0
        )
        assert not armed.on_burn(1.5)  # below threshold

    def test_endpoint_503_when_unavailable(self):
        import urllib.error
        import urllib.request

        srv = MetricsServer(
            Registry("cometbft"),
            profiler=proflib.ProfilerCapture(profile_dir=None),
        )
        port = srv.serve("127.0.0.1", 0)
        try:
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/debug/profile", timeout=5
                )
            assert exc_info.value.code == 503
        finally:
            srv.stop()

    @pytest.mark.slow
    def test_capture_e2e_and_retention(self, tmp_path):
        """A real bounded jax.profiler capture: the dir must contain a
        loadable trace (an .xplane.pb under plugins/profile is what the
        JAX toolchain's trace viewer opens), and keep-N retention must
        prune the oldest captures."""
        prof = proflib.ProfilerCapture(profile_dir=str(tmp_path), keep=2)
        assert prof.available()
        paths = [
            prof.capture(duration_ms=50, reason=f"test{i}")
            for i in range(3)
        ]
        assert all(p is not None for p in paths)
        files = []
        for root, _dirs, names in os.walk(paths[-1]):
            files.extend(os.path.join(root, n) for n in names)
        assert files, "capture produced no trace files"
        assert any(f.endswith(".xplane.pb") for f in files)
        kept = [
            d for d in os.listdir(tmp_path) if d.startswith("profile_")
        ]
        assert len(kept) == 2  # keep-N pruned the oldest
        last = prof.last_capture()
        assert last is not None and last["path"] == paths[-1]

    @pytest.mark.slow
    def test_endpoint_runs_capture(self, tmp_path):
        import urllib.request

        srv = MetricsServer(
            Registry("cometbft"),
            profiler=proflib.ProfilerCapture(profile_dir=str(tmp_path)),
        )
        port = srv.serve("127.0.0.1", 0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/profile?ms=50", timeout=30
            ).read()
            doc = json.loads(body)
            assert os.path.isdir(doc["path"])
        finally:
            srv.stop()


class TestBenchHistorySentinel:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, os.path.join(_TOOLS, "bench_history.py"),
             *args],
            capture_output=True, text=True, timeout=60,
        )

    def test_self_test_passes(self):
        """Satellite 6's fast tier-1 check: the synthetic ledger with an
        injected 20% regression must flag (and the clean/blip ledgers
        must pass) inside the tool's own --self-test."""
        res = self._run("--self-test")
        assert res.returncode == 0, res.stdout + res.stderr
        assert "SELF-TEST PASS" in res.stdout

    def test_real_ledger_check_passes(self):
        res = self._run("--check")
        assert res.returncode == 0, res.stdout + res.stderr

    def test_append_wraps_stage_records(self, tmp_path):
        ledger = tmp_path / "hist.jsonl"
        rec = tmp_path / "stage.json"
        rec.write_text(json.dumps({"first_verdict_ms": 120.0}))
        res = self._run(
            "--append", str(rec), "--stage", "coldboot",
            "--ledger", str(ledger),
        )
        assert res.returncode == 0, res.stdout + res.stderr
        lines = ledger.read_text().splitlines()
        assert len(lines) == 1
        row = json.loads(lines[0])
        assert row["metric"] == "bench_stage_coldboot"
        assert row["stages"]["coldboot"]["first_verdict_ms"] == 120.0

    def test_synthetic_sustained_regression_flagged(self, tmp_path):
        ledger = tmp_path / "hist.jsonl"
        rows = [
            {"metric": "m", "unit": "sigs/sec", "value": 1000.0 + i}
            for i in range(5)
        ] + [
            {"metric": "m", "unit": "sigs/sec", "value": 800.0},
            {"metric": "m", "unit": "sigs/sec", "value": 799.0},
        ]
        ledger.write_text(
            "".join(json.dumps(r) + "\n" for r in rows)
        )
        res = self._run("--check", "--ledger", str(ledger))
        assert res.returncode == 1
        assert '"path": "value"' in res.stdout
