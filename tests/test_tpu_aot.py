"""AOT shape-bucket precompilation (crypto/tpu/aot.py).

Pins the PR's acceptance contract: after a warm boot covering a bucket,
a real verify_batch dispatch at that bucket triggers ZERO new XLA
compilations (registry miss counter unchanged). Plus the degradation
paths: corrupt/truncated executable-store entries recompile fresh with
a warning, fingerprint changes invalidate instead of trusting stale
executables, stale kernel ids are never resolved to a live name, and a
mid-warmup stop() joins within one compile.

Toy kernels keep everything except the acceptance test off the
expensive ed25519 program.
"""

import glob
import os
import pickle
import threading
import time
import weakref

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.tpu import aot, calibrate


def _toy_kernel():
    import jax

    @jax.jit
    def parity_kernel(rows):
        return (rows.sum(axis=0) % 2) == 0

    return parity_kernel


def _rows(bucket, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 100, size=(3, bucket)).astype(np.int32)


@pytest.fixture
def no_store():
    """Registry tests that count compiles exactly: disable the disk
    executable store (conftest's .jax_cache would otherwise serve
    deserialized executables and skew the counts)."""
    aot.configure_exec_store("")
    yield
    aot.configure_exec_store(None)


@pytest.fixture
def tmp_store(tmp_path):
    root = str(tmp_path / "aot_exec")
    aot.configure_exec_store(root)
    yield root
    aot.configure_exec_store(None)


class TestEd25519Registrations:
    def test_three_programs_two_warmed(self):
        """A keyed ed25519 flush has one program: the registry holds the
        compact, resident and indexed kernels and nothing else of the
        curve, and the warm plan's input is the compact and resident
        ones, with their bucket shapes and flags."""
        from cometbft_tpu.crypto.tpu import ed25519_batch as eb

        regs = {
            name: r for name, r in aot._registered.items()
            if name.startswith("ed25519.")
        }
        assert set(regs) == {
            "ed25519.verify_compact", "ed25519.verify_resident",
            "ed25519.verify_indexed",
        }
        warm = [
            r.name for r in aot.registered_kernels()
            if r.name.startswith("ed25519.")
        ]
        assert warm == ["ed25519.verify_resident", "ed25519.verify_compact"]
        compact = regs["ed25519.verify_compact"]
        assert compact.kernel is eb.verify_kernel_compact
        assert compact.bucket_shapes(64) == [((128, 64), np.uint8)]
        assert (compact.forced, compact.launch, compact.donate_from) == (
            True, eb._LAUNCH_LANES, 0,
        )
        resident = regs["ed25519.verify_resident"]
        assert resident.bucket_shapes(64) == [
            ((8, 64), np.uint32), ((24, 64), np.uint32),
        ]
        assert (resident.forced, resident.launch, resident.donate_from) == (
            False, eb._LAUNCH_LANES, 1,
        )
        indexed = regs["ed25519.verify_indexed"]
        assert indexed.bucket_shapes is None
        assert (indexed.forced, indexed.launch, indexed.donate_from) == (
            False, None, 1,
        )


class TestStableKernelName:
    def test_registration_wins_and_pins(self):
        k = _toy_kernel()
        aot.register_kernel("test.toy_registered", k)
        assert aot.stable_kernel_name(k) == "test.toy_registered"
        # repeated: same answer, not a fresh serial
        assert aot.stable_kernel_name(k) == "test.toy_registered"

    def test_distinct_objects_same_qualname_get_serials(self):
        def make():
            def inner(x):
                return x

            return inner

        a, b = make(), make()
        na, nb = aot.stable_kernel_name(a), aot.stable_kernel_name(b)
        assert na != nb
        assert nb.startswith(na.split("#")[0])

    def test_id_reuse_after_gc_is_detected_not_trusted(self):
        """The id()-keyed bug this module fixes: a NEW object occupying
        a dead kernel's id must get a fresh name, never the dead one's
        (which would run the wrong executable)."""

        def make():
            def victim(x):
                return x

            return victim

        old = make()
        old_name = aot.stable_kernel_name(old)
        new = make()
        # simulate CPython id reuse: bind the dead kernel's name to the
        # new object's id, liveness-guarded by a weakref about to die
        with aot._name_mtx:
            aot._name_by_id[id(new)] = (old_name, weakref.ref(old), None)
        del old
        import gc

        gc.collect()
        assert aot.stable_kernel_name(new) != old_name


class TestExecutableRegistry:
    def test_miss_compiles_hit_reuses_and_runs_right(self, no_store):
        import jax

        reg = aot.ExecutableRegistry()
        k = _toy_kernel()
        rows = _rows(64)
        placed = [jax.device_put(rows, jax.devices("cpu")[0])]
        out1 = np.asarray(reg.call(k, placed))
        assert (out1 == ((rows.sum(axis=0) % 2) == 0)).all()
        s = reg.stats()
        assert (s["misses"], s["hits"], s["compiles"]) == (1, 0, 1)
        out2 = np.asarray(reg.call(k, [jax.device_put(rows, jax.devices("cpu")[0])]))
        assert (out2 == out1).all()
        s = reg.stats()
        assert (s["misses"], s["hits"], s["compiles"]) == (1, 1, 1)
        # a different bucket is a different executable
        reg.warm(k, [((3, 128), np.int32)])
        assert reg.compile_count == 2

    def test_lru_bound_evicts_and_recompiles(self, no_store):
        reg = aot.ExecutableRegistry(max_entries=2)
        k = _toy_kernel()
        for bucket in (64, 128, 256):
            reg.warm(k, [((3, bucket), np.int32)])
        assert len(reg) == 2
        assert reg.metrics.evictions.value() == 1
        assert reg.compile_count == 3
        # 64 was evicted (LRU) → warming it again is a real compile
        assert reg.warm(k, [((3, 64), np.int32)]) > 0.0
        assert reg.compile_count == 4

    def test_fingerprint_change_invalidates_never_trusts(
        self, no_store, monkeypatch
    ):
        reg = aot.ExecutableRegistry()
        k = _toy_kernel()
        reg.warm(k, [((3, 64), np.int32)])
        assert len(reg) == 1 and reg.compile_count == 1
        monkeypatch.setattr(
            aot, "backend_fingerprint", lambda: "other-jax:tpu:v9:8"
        )
        # the entry compiled against the old backend is discarded and
        # the same (kernel, bucket) recompiles under the new fingerprint
        assert reg.warm(k, [((3, 64), np.int32)]) > 0.0
        assert reg.compile_count == 2
        assert reg.metrics.invalidations.value() == 1
        assert len(reg) == 1

    def test_racing_misses_compile_once(self, no_store):
        reg = aot.ExecutableRegistry()
        k = _toy_kernel()
        orig = reg._build
        started = threading.Event()

        def slow_build(*a, **kw):
            started.set()
            time.sleep(0.3)
            return orig(*a, **kw)

        reg._build = slow_build
        outs = []

        def worker():
            outs.append(reg.warm(k, [((3, 64), np.int32)]))

        ts = [threading.Thread(target=worker) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        # one leader compiles; the others either followed the in-flight
        # build or (if scheduled late) hit the finished entry — never a
        # second compile of the same key
        assert reg.compile_count == 1
        assert reg.metrics.registry_misses.value() >= 1

    def test_compile_failure_retries_fresh_once(self, no_store):
        reg = aot.ExecutableRegistry()
        k = _toy_kernel()
        orig = reg._build
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("truncated persistent cache entry")
            return orig(*a, **kw)

        reg._build = flaky
        with pytest.warns(RuntimeWarning, match="retrying with a fresh"):
            secs = reg.warm(k, [((3, 64), np.int32)])
        assert secs > 0.0
        assert len(calls) == 2
        assert reg.metrics.compile_fallbacks.value() == 1


class TestExecutableStore:
    def test_second_registry_loads_without_compiling(self, tmp_store):
        k = _toy_kernel()
        reg1 = aot.ExecutableRegistry()
        reg1.warm(k, [((3, 64), np.int32)])
        assert reg1.compile_count == 1
        assert glob.glob(os.path.join(tmp_store, "*.aotexe"))
        # a fresh registry (new process boot) deserializes — no trace,
        # no lower, no compile
        reg2 = aot.ExecutableRegistry()
        assert reg2.warm(k, [((3, 64), np.int32)]) == 0.0
        assert reg2.compile_count == 0
        assert reg2.metrics.exec_store_hits.value() == 1
        # and the loaded executable actually runs correctly
        import jax

        rows = _rows(64)
        out = np.asarray(
            reg2.call(k, [jax.device_put(rows, jax.devices("cpu")[0])])
        )
        assert (out == ((rows.sum(axis=0) % 2) == 0)).all()

    def test_corrupt_entry_warns_and_recompiles(self, tmp_store):
        k = _toy_kernel()
        aot.ExecutableRegistry().warm(k, [((3, 64), np.int32)])
        (path,) = glob.glob(os.path.join(tmp_store, "*.aotexe"))
        with open(path, "wb") as fh:
            fh.write(b"\x00garbage not a pickle")
        reg = aot.ExecutableRegistry()
        with pytest.warns(RuntimeWarning, match="unreadable"):
            secs = reg.warm(k, [((3, 64), np.int32)])
        assert secs > 0.0 and reg.compile_count == 1
        # the corrupt file was discarded and replaced by the fresh build
        (path2,) = glob.glob(os.path.join(tmp_store, "*.aotexe"))
        with open(path2, "rb") as fh:
            assert fh.read(20) != b"\x00garbage not a pick"

    def test_entry_that_loads_but_does_not_run_is_discarded(self, tmp_store):
        """The store proves an entry when it loads it (one run on zeros):
        one that deserializes and then fails its first call is thrown
        away, counted, and compiled fresh — never handed to dispatch."""
        import shutil

        import jax

        k = _toy_kernel()
        reg1 = aot.ExecutableRegistry()
        reg1.warm(k, [((3, 64), np.int32)])
        (path,) = glob.glob(os.path.join(tmp_store, "*.aotexe"))
        # the 64-lane executable filed under the 128-lane key
        sds = [jax.ShapeDtypeStruct((3, 128), np.int32)]
        key, _, _ = reg1._key(k, reg1._shape_key(sds), 0, False)
        shutil.copy(path, aot.ExecutableStore(tmp_store)._path(key))
        reg = aot.ExecutableRegistry()
        with pytest.warns(RuntimeWarning, match="does not run"):
            assert reg.warm(k, [((3, 128), np.int32)]) > 0.0
        assert reg.compile_count == 1
        assert reg.metrics.exec_store_discards.value() == 1
        assert reg.metrics.exec_store_hits.value() == 0

    def test_truncated_entry_warns_and_recompiles(self, tmp_store):
        k = _toy_kernel()
        aot.ExecutableRegistry().warm(k, [((3, 64), np.int32)])
        (path,) = glob.glob(os.path.join(tmp_store, "*.aotexe"))
        blob = open(path, "rb").read()
        with open(path, "wb") as fh:
            fh.write(blob[: len(blob) // 3])
        reg = aot.ExecutableRegistry()
        with pytest.warns(RuntimeWarning, match="unreadable"):
            assert reg.warm(k, [((3, 64), np.int32)]) > 0.0
        assert reg.compile_count == 1

    def test_wrong_structure_entry_warns_and_recompiles(self, tmp_store):
        k = _toy_kernel()
        aot.ExecutableRegistry().warm(k, [((3, 64), np.int32)])
        (path,) = glob.glob(os.path.join(tmp_store, "*.aotexe"))
        with open(path, "wb") as fh:
            # valid pickle, not a serialized executable triple
            pickle.dump((b"payload", "in_tree", "out_tree"), fh)
        reg = aot.ExecutableRegistry()
        with pytest.warns(RuntimeWarning, match="failed to\\s+deserialize"):
            assert reg.warm(k, [((3, 64), np.int32)]) > 0.0
        assert reg.compile_count == 1


class TestBucketLadder:
    def test_p50_first_cap_last_nothing_below_the_floor(self, monkeypatch):
        """The ladder is what routing can pad a flush to: min_batch's
        bucket up to max_chunk."""
        monkeypatch.setattr(calibrate, "compile_seconds", lambda *a: {})
        assert aot.bucket_ladder(floor=1024, cap=8192) == [
            1024, 2048, 4096, 8192,
        ]
        assert aot.bucket_ladder(floor=1, cap=256) == [64, 128, 256]

    def test_plan_warms_the_canary_bucket_first(self, monkeypatch):
        """Below the floor only the canary's bucket is warmed, first, for
        the kernel the canary dispatches — and not for the others. Above
        it the single-device ladder of a kernel ends at the launch its
        entry streams at (register_kernel's ``launch``): no ed25519 flush
        on one chip reaches @4096 or @8192."""
        monkeypatch.setattr(calibrate, "compile_seconds", lambda *a: {})
        plan = [
            (t.name, t.bucket) for t in
            aot.warmup_plan(floor=1024, include_single=True)
            # the ed25519 entries: a kernel another test file registered
            # in this process without a ``launch`` keeps the whole ladder
            if not t.sharded and t.name.startswith("ed25519.")
        ]
        assert plan[0] == ("ed25519.verify_compact", 64)
        assert [b for _, b in plan].count(64) == 1
        assert {b for _, b in plan[1:]} == {1024, 2048}
        assert ("ed25519.verify_resident", 2048) in plan
        assert ("ed25519.verify_compact", 2048) in plan
        # the sharded variants keep the whole ladder: a launch over a
        # mesh is capped in total, 8,192 lanes over four chips
        sharded = {
            t.bucket for t in aot.warmup_plan(floor=1024) if t.sharded
        }
        assert sharded == {1024, 2048, 4096, 8192}
        # an explicit size list is taken as given
        sized = aot.warmup_plan(sizes=[2048, 8192])
        assert {t.bucket for t in sized if not t.sharded} == {2048, 8192}

    def test_measured_compile_cost_reorders_above_floor(self, monkeypatch):
        monkeypatch.setattr(
            calibrate,
            "compile_seconds",
            lambda *a: {4096: 0.1, 2048: 5.0},
        )
        # cheap measured buckets warm first; unmeasured 8192 keys by
        # size and stays last
        assert aot.bucket_ladder(floor=1024, cap=8192) == [
            1024, 4096, 2048, 8192,
        ]

    def test_floor_above_cap_clamps(self, monkeypatch):
        monkeypatch.setattr(calibrate, "compile_seconds", lambda *a: {})
        assert aot.bucket_ladder(floor=100_000, cap=256) == [256]


class TestWarmBootLifecycle:
    @pytest.fixture(autouse=True)
    def _clean_handle(self):
        yield
        aot.stop_warm_boot(timeout=5.0)

    def test_stop_mid_warmup_joins_within_bound(self):
        compiling = threading.Event()

        def body(stop_event):
            # a warm boot that would take ~5 s unless stopped between
            # "compiles" (the run_warm_boot contract)
            for _ in range(100):
                compiling.set()
                if stop_event.is_set():
                    return "stopped"
                time.sleep(0.05)
            return "ran dry"

        wb = aot.start_warm_boot("background", body=body)
        assert aot.current_warm_boot() is wb
        assert compiling.wait(5)
        t0 = time.perf_counter()
        assert aot.stop_warm_boot(timeout=5.0) is True
        assert time.perf_counter() - t0 < 2.0
        assert wb.result == "stopped"
        assert aot.current_warm_boot() is None

    def test_pre_set_stop_event_warms_nothing(self):
        reg = aot.ExecutableRegistry()
        stop = threading.Event()
        stop.set()
        obs = aot.run_warm_boot(
            sizes=[64], registry=reg, stop_event=stop
        )
        assert obs == []
        assert reg.compile_count == 0
        assert reg.metrics.warmup_state.value() == 3  # stopped

    def test_eager_swallows_body_failure(self):
        def body(stop_event):
            raise RuntimeError("device plane down")

        wb = aot.start_warm_boot("eager", body=body)
        assert wb.done
        assert isinstance(wb.error, RuntimeError)

    def test_off_is_a_noop(self):
        aot.stop_warm_boot()
        assert aot.start_warm_boot("off") is None
        assert aot.current_warm_boot() is None

    def test_background_result_and_join(self):
        wb = aot.start_warm_boot("background", body=lambda stop: 42)
        assert wb.join(timeout=5.0) is True
        assert wb.result == 42 and wb.error is None

    def test_supervisor_canary_joins_warm_boot(self):
        """The supervisor's warmup canary must not probe (and declare
        HEALTHY) until the warm boot finishes or the watchdog bound
        expires."""
        from cometbft_tpu.crypto.batch import BackendSpec
        from cometbft_tpu.crypto.supervisor import BackendSupervisor

        release = threading.Event()
        order = []

        def body(stop_event):
            release.wait(10)
            order.append("warm")

        wb = aot.start_warm_boot("background", body=body)
        sup = BackendSupervisor(
            spec=BackendSpec("cpu"), dispatch_timeout_ms=30_000
        )
        probed = threading.Event()
        orig = sup.probe_now

        def probe_spy(*a, **kw):
            order.append("probe")
            probed.set()
            return orig(*a, **kw)

        sup.probe_now = probe_spy
        try:
            sup.warmup_canary()
            assert not probed.wait(0.5)  # still joined on the warm boot
            release.set()
            assert probed.wait(10)
            assert order == ["warm", "probe"]
            assert wb.done
        finally:
            release.set()
            sup.stop()


class TestWarmBootMode:
    def test_env_beats_config_beats_default(self, monkeypatch):
        monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
        monkeypatch.delenv("CBFT_TPU_WARMUP", raising=False)
        assert aot.warm_boot_mode() == "background"
        assert aot.warm_boot_mode("eager") == "eager"
        monkeypatch.setenv("CBFT_WARM_BOOT", "off")
        assert aot.warm_boot_mode("eager") == "off"

    def test_legacy_kill_switch_forces_off(self, monkeypatch):
        monkeypatch.setenv("CBFT_TPU_WARMUP", "0")
        monkeypatch.setenv("CBFT_WARM_BOOT", "eager")
        assert aot.warm_boot_mode("background") == "off"

    def test_invalid_mode_rejected(self, monkeypatch):
        monkeypatch.delenv("CBFT_TPU_WARMUP", raising=False)
        monkeypatch.setenv("CBFT_WARM_BOOT", "sideways")
        with pytest.raises(ValueError, match="warm_boot"):
            aot.warm_boot_mode()

    def test_config_validate_rejects_bad_value(self):
        from cometbft_tpu.config import Config

        cfg = Config()
        assert cfg.crypto.warm_boot == "background"
        cfg.crypto.warm_boot = "sideways"
        with pytest.raises(ValueError, match="warm_boot"):
            cfg.validate_basic()


class TestCompileCalibration:
    @pytest.fixture
    def table(self, tmp_path, monkeypatch):
        monkeypatch.delenv("CBFT_TPU_CALIBRATION", raising=False)
        path = str(tmp_path / "tpu_calibration.json")
        calibrate.set_table_path(path)
        yield path
        calibrate.set_table_path(None)

    def test_merge_and_read_back_per_topology(self, table):
        obs = [
            {"kernel": "k", "bucket": 64, "sharded": True,
             "topology": "cpu:8", "compile_s": 1.25, "cached": False},
            {"kernel": "k2", "bucket": 64, "sharded": False,
             "topology": "cpu:8", "compile_s": 0.75, "cached": False},
            {"kernel": "k", "bucket": 128, "sharded": True,
             "topology": "cpu:8", "compile_s": 3.0, "cached": False},
            # cached observations measure the cache, not the compiler
            {"kernel": "k", "bucket": 256, "sharded": True,
             "topology": "cpu:8", "compile_s": 0.0, "cached": True},
        ]
        assert calibrate.merge_compile_times(obs) is not None
        got = calibrate.compile_seconds("cpu:8")
        assert got == {64: 2.0, 128: 3.0}
        assert calibrate.compile_seconds("tpu:64") == {}

    def test_min_compile_secs_tracks_cheapest_observation(self, table):
        assert calibrate.persistent_cache_min_compile_secs() == 5.0
        calibrate.merge_compile_times([
            {"kernel": "k", "bucket": 64, "sharded": True,
             "topology": "cpu:8", "compile_s": 1.2, "cached": False},
        ])
        # half the cheapest compile: every warm-boot build is admitted
        assert calibrate.persistent_cache_min_compile_secs() == pytest.approx(
            0.6
        )

    def test_min_compile_secs_floors_at_point_one(self, table):
        calibrate.merge_compile_times([
            {"kernel": "k", "bucket": 64, "sharded": False,
             "topology": "cpu:8", "compile_s": 0.05, "cached": False},
        ])
        assert calibrate.persistent_cache_min_compile_secs() == 0.1


class TestZeroCompileDispatch:
    """The PR acceptance contract, end to end on the real ed25519
    kernels and the 8-device virtual mesh."""

    def test_warmed_bucket_dispatches_with_zero_new_compiles(self):
        from cometbft_tpu.crypto.tpu import ed25519_batch, mesh

        assert mesh.n_devices() == 8
        # warm the 64 bucket (sharded — what 8-device dispatch runs)
        obs = aot.run_warm_boot(sizes=[64], include_single=False)
        assert obs and all(ob["topology"] for ob in obs)
        reg = aot.default_registry()
        compiles = reg.compile_count
        misses = reg.metrics.registry_misses.value()
        hits = reg.metrics.registry_hits.value()

        keys = [ed.gen_priv_key_from_secret(bytes([i, 99])) for i in range(40)]
        pks, msgs, sigs = [], [], []
        for i, k in enumerate(keys):
            m = b"warm dispatch %d" % i
            s = bytearray(k.sign(m))
            if i % 7 == 0:
                s[3] ^= 1
            pks.append(k.pub_key().bytes())
            msgs.append(m)
            sigs.append(bytes(s))
        got = ed25519_batch.verify_batch(pks, msgs, sigs)  # 40 → pad 64
        want = [
            ed.PubKeyEd25519(p).verify_signature(m, s)
            for p, m, s in zip(pks, msgs, sigs)
        ]
        assert got == want
        # the dispatch was a pure registry hit: no new executable, no
        # new miss — nothing on the hot path paid trace+compile
        assert reg.compile_count == compiles
        assert reg.metrics.registry_misses.value() == misses
        assert reg.metrics.registry_hits.value() > hits

    def test_single_device_variant_also_warms_to_a_hit(self):
        import jax

        from cometbft_tpu.crypto.tpu import ed25519_batch

        # the degraded-to-one-device fallback variant is part of the
        # default plan (include_single); a lookup at the warmed bucket
        # must be a hit too
        aot.run_warm_boot(sizes=[64], include_single=True)
        reg = aot.default_registry()
        compiles = reg.compile_count
        misses = reg.metrics.registry_misses.value()
        reg.lookup(
            ed25519_batch.verify_kernel_compact,
            [jax.ShapeDtypeStruct((128, 64), np.uint8)],
            sharded=False,
        )
        assert reg.compile_count == compiles
        assert reg.metrics.registry_misses.value() == misses
