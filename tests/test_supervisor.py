"""BackendSupervisor: circuit breaker, dispatch watchdog, corruption
audit, fault injection, and the scheduler robustness satellites.

Contract under test (crypto/supervisor.py, crypto/faults.py,
crypto/scheduler.py, crypto/tpu/mesh.py):
  - verdicts ALWAYS match the CPU ground truth, under every injected
    failure mode (exceptions, hangs, silent corruption, sudden death,
    jitter);
  - the breaker walks HEALTHY → DEGRADED → BROKEN exactly as specced
    and canary probes re-admit the backend after it recovers;
  - a wedged dispatch is abandoned within dispatch_timeout_ms and the
    zombie thread exits early through the mesh cancel event;
  - submit() is bounded by [crypto] max_queue and degrades to inline
    CPU verification when the deadline expires — no future lost;
  - stop() detects a failed worker join and fails pending futures
    instead of leaving callers blocked.
"""

import threading
import time

import pytest

from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.batch import (
    BackendSpec,
    CPUBatchVerifier,
    new_batch_verifier,
    unwrap_backend,
)
from cometbft_tpu.crypto.faults import (
    FaultInjected,
    FaultPlan,
    FaultyBackend,
    install,
    run_chaos_soak,
)
from cometbft_tpu.crypto.scheduler import VerifyScheduler
from cometbft_tpu.crypto.supervisor import (
    BROKEN,
    DEGRADED,
    HEALTHY,
    BackendSupervisor,
    SupervisedBatchVerifier,
    WatchdogTimeout,
    audit_pct_default,
    breaker_threshold_default,
    dispatch_timeout_ms_default,
)


def _make_items(n, tag=b"", poison_at=None):
    items = []
    for i in range(n):
        k = ed.gen_priv_key_from_secret(tag + bytes([i & 0xFF, i >> 8]))
        msg = b"supervisor-msg-" + tag + i.to_bytes(4, "big")
        sig = k.sign(msg)
        if poison_at is not None and i == poison_at:
            sig = b"\x00" * 64
        items.append((k.pub_key(), msg, sig))
    return items


def _cpu_mask(items):
    bv = CPUBatchVerifier()
    for pk, m, s in items:
        bv.add(pk, m, s)
    _, mask = bv.verify()
    return mask


_seq = [0]


def _faulty(plan=None, **sup_kwargs):
    """A fresh FaultyBackend registration + supervisor over it (unique
    backend name per call — the registry is process-global)."""
    _seq[0] += 1
    name = f"test-faulty-{_seq[0]}"
    plan = install(name=name, inner="cpu",
                   plan=plan if plan is not None else FaultPlan(seed=_seq[0]))
    sup_kwargs.setdefault("dispatch_timeout_ms", 2000)
    sup_kwargs.setdefault("breaker_threshold", 3)
    sup_kwargs.setdefault("audit_pct", 0)
    sup_kwargs.setdefault("probe_base_ms", 10)
    sup_kwargs.setdefault("probe_max_ms", 80)
    sup = BackendSupervisor(spec=BackendSpec(name), **sup_kwargs)
    return plan, sup


class TestBreakerStateMachine:
    def test_healthy_path_verdicts_and_state(self):
        plan, sup = _faulty()
        items = _make_items(8, poison_at=3)
        assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.state() == HEALTHY
        # mixed verdicts cost one extra device pass: the triage re-check
        # that convicts the poisoned lane (tests/test_adaptive_dispatch.py)
        assert sup.metrics.device_dispatches.value() == 2
        assert sup.metrics.triage_runs.value() == 1
        sup.stop()

    def test_failures_walk_healthy_degraded_broken(self):
        plan, sup = _faulty(breaker_threshold=3)
        items = _make_items(4)
        plan.exception_rate = 1.0
        assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.state() == DEGRADED
        assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.state() == DEGRADED
        assert sup.verify_items(items) == _cpu_mask(items)  # 3rd → trip
        assert sup.state() == BROKEN
        assert sup.metrics.trips.with_labels(cause="failures").value() == 1
        assert sup.metrics.failures.value() == 3
        sup.stop()

    def test_success_recovers_degraded_to_healthy(self):
        plan, sup = _faulty(breaker_threshold=3)
        items = _make_items(4)
        plan.exception_rate = 1.0
        sup.verify_items(items)
        assert sup.state() == DEGRADED
        plan.clear()
        assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.state() == HEALTHY
        sup.stop()

    def test_broken_routes_to_cpu_without_touching_backend(self):
        plan, sup = _faulty(breaker_threshold=1)
        items = _make_items(4, poison_at=1)
        plan.exception_rate = 1.0
        sup.verify_items(items)
        assert sup.state() == BROKEN
        before = plan.dispatches
        for _ in range(3):
            assert sup.verify_items(items) == _cpu_mask(items)
        # the breaker short-circuits: no new backend dispatches (the
        # lazy async probe may fire, so allow at most probe traffic)
        assert sup.metrics.cpu_routed.value() == 3
        assert plan.dispatches - before <= 3  # probes only, not traffic
        sup.stop()

    def test_success_does_not_close_open_breaker(self):
        # only a canary probe may close BROKEN — a lucky dispatch must not
        plan, sup = _faulty(breaker_threshold=1)
        plan.exception_rate = 1.0
        sup.verify_items(_make_items(2))
        assert sup.state() == BROKEN
        plan.clear()
        sup._note_success(sup._domains[0])
        assert sup.state() == BROKEN
        sup.stop()

    def test_probe_readmits_after_recovery(self):
        plan, sup = _faulty(breaker_threshold=1)
        plan.exception_rate = 1.0
        sup.verify_items(_make_items(2))
        assert sup.state() == BROKEN
        plan.clear()
        assert sup.probe_now() is True
        assert sup.state() == HEALTHY
        assert sup.metrics.probes.with_labels(outcome="ok").value() == 1
        # traffic flows back to the device
        before = plan.dispatches
        items = _make_items(4)
        assert sup.verify_items(items) == _cpu_mask(items)
        assert plan.dispatches == before + 1
        sup.stop()

    def test_failed_probe_doubles_backoff_capped(self):
        plan, sup = _faulty(breaker_threshold=1, probe_base_ms=10,
                            probe_max_ms=40)
        plan.die_after = 0
        sup.verify_items(_make_items(2))
        assert sup.state() == BROKEN
        assert sup._backoff_s == pytest.approx(0.010)
        assert sup.probe_now() is False
        assert sup._backoff_s == pytest.approx(0.020)
        assert sup.probe_now() is False
        assert sup._backoff_s == pytest.approx(0.040)
        assert sup.probe_now() is False
        assert sup._backoff_s == pytest.approx(0.040)  # capped
        assert sup.metrics.probes.with_labels(outcome="fail").value() == 3
        sup.stop()

    def test_empty_and_cpu_spec_bypass_supervision(self):
        sup = BackendSupervisor(spec=BackendSpec("cpu"))
        assert sup.verify_items([]) == []
        items = _make_items(3, poison_at=0)
        assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.metrics.device_dispatches.value() == 0
        sup.stop()


class TestWatchdog:
    def test_hang_is_abandoned_and_breaks_circuit(self):
        plan, sup = _faulty(dispatch_timeout_ms=200, breaker_threshold=3)
        plan.hang_rate = 1.0
        plan.hang_s = 30.0
        items = _make_items(4, poison_at=2)
        t0 = time.perf_counter()
        mask = sup.verify_items(items)
        dt = time.perf_counter() - t0
        assert mask == _cpu_mask(items)  # CPU re-verify, exact verdicts
        assert dt < 5.0, f"watchdog did not bound the hang ({dt:.1f}s)"
        # ANY watchdog trip opens the breaker immediately
        assert sup.state() == BROKEN
        assert sup.metrics.watchdog_kills.value() == 1
        assert sup.metrics.trips.with_labels(cause="watchdog").value() == 1
        sup.stop()

    def test_zombie_thread_exits_via_cancel_event(self):
        plan, sup = _faulty(dispatch_timeout_ms=200)
        plan.hang_rate = 1.0
        plan.hang_s = 30.0
        sup.verify_items(_make_items(2))
        # the abandoned thread wakes on the cancel event, NOT after 30 s
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            zombies = [
                t for t in threading.enumerate()
                if t.name == "supervised-dispatch" and t.is_alive()
            ]
            if not zombies:
                break
            time.sleep(0.02)
        assert not zombies, "abandoned dispatch thread still alive"
        sup.stop()

    def test_building_an_executable_does_not_run_the_watchdog(self):
        """A dispatch that first has to build its executable (a cold
        bucket compiles for 45-85 s on a v5e) is not a hung device: the
        seconds its worker spends inside a registry miss are left out of
        dispatch_timeout_ms; the same seconds spent any other way are a
        watchdog kill."""
        from cometbft_tpu.crypto.tpu import aot

        class _Slow(CPUBatchVerifier):
            building = True

            def verify(self):
                clock = aot.build_clock()
                if self.building:
                    clock.since = time.monotonic()
                time.sleep(0.6)
                if self.building:
                    clock.seconds += time.monotonic() - clock.since
                    clock.since = None
                return super().verify()

        cryptobatch.register_backend("test-slow-build", _Slow)
        sup = BackendSupervisor(
            spec=cryptobatch.BackendSpec("test-slow-build"),
            dispatch_timeout_ms=200, audit_pct=0, hedge_pct=0,
        )
        items = _make_items(4, poison_at=1)
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.watchdog_kills.value() == 0
            assert sup.state() == HEALTHY
            # what the latency model learned is the dispatch, not the build
            seen = sup._domains[0].latency_model._buckets
            assert all(ewma_s < 0.3 for _, ewma_s, _ in seen.values()), seen
            assert sup.metrics.failures.value() == 0
            _Slow.building = False
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.watchdog_kills.value() == 1
        finally:
            sup.stop()

    def test_watchdog_timeout_type(self):
        plan, sup = _faulty(dispatch_timeout_ms=100)
        plan.hang_rate = 1.0
        plan.hang_s = 30.0
        with pytest.raises(WatchdogTimeout):
            sup._device_verify(sup._domains[0], _make_items(2))
        sup.stop()


class TestCorruptionAudit:
    def test_sync_audit_catches_corruption_before_release(self):
        plan, sup = _faulty(audit_pct=100, audit_sync=True)
        items = _make_items(6, poison_at=4)
        plan.corrupt_rate = 1.0
        # the device verdict is flipped; triage overturns the false
        # convictions (one mismatch), then the sync audit catches the
        # false accept on the poisoned lane BEFORE release (a second
        # mismatch) and the ground truth wins
        assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.state() == BROKEN
        assert sup.metrics.audit_mismatches.value() == 2
        assert sup.metrics.trips.with_labels(cause="audit").value() == 1
        sup.stop()

    def test_async_audit_breaks_circuit_in_background(self):
        plan, sup = _faulty(audit_pct=100, audit_sync=False)
        # all signatures bad: corruption flips the mask to all-True, an
        # all-ok verdict that triage never re-checks (triage only chases
        # claimed-BAD lanes) — the classic silent false accept
        items = [(pk, m, b"\x00" * 64) for pk, m, _ in _make_items(6)]
        plan.corrupt_rate = 1.0
        mask = sup.verify_items(items)
        # background mode: the corrupted verdict escapes THIS batch...
        assert mask == [True] * 6
        # ...but the audit catches it and breaks the circuit shortly
        deadline = time.monotonic() + 10.0
        while sup.state() != BROKEN and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sup.state() == BROKEN
        assert sup.metrics.audit_mismatches.value() == 1
        sup.stop()

    def test_clean_batches_audit_without_tripping(self):
        plan, sup = _faulty(audit_pct=100, audit_sync=True)
        items = _make_items(5, poison_at=1)
        for _ in range(3):
            assert sup.verify_items(items) == _cpu_mask(items)
        assert sup.state() == HEALTHY
        assert sup.metrics.audits.value() == 3
        assert sup.metrics.audit_mismatches.value() == 0
        sup.stop()

    def test_audit_pct_zero_never_audits(self):
        plan, sup = _faulty(audit_pct=0)
        sup.verify_items(_make_items(4))
        assert sup.metrics.audits.value() == 0
        sup.stop()


class TestVerdictParityAllModes:
    @pytest.mark.parametrize("mode", [
        "exceptions", "dead", "corruption_sync", "jitter", "hang",
    ])
    def test_mode_never_releases_wrong_verdict(self, mode):
        kwargs = {}
        plan = FaultPlan(seed=hash(mode) & 0xFFFF)
        if mode == "exceptions":
            plan.exception_rate = 0.6
        elif mode == "dead":
            plan.die_after = 2
        elif mode == "corruption_sync":
            plan.corrupt_rate = 0.5
            kwargs = {"audit_pct": 100, "audit_sync": True}
        elif mode == "jitter":
            plan.jitter_ms = 3.0
        elif mode == "hang":
            plan.hang_rate = 0.4
            plan.hang_s = 30.0
            kwargs = {"dispatch_timeout_ms": 150}
        _, sup = _faulty(plan=plan, **kwargs)
        for i in range(6):
            items = _make_items(8, tag=bytes([i]),
                                poison_at=i % 8 if i % 2 else None)
            assert sup.verify_items(items) == _cpu_mask(items), mode
        sup.stop()


class TestFaultyBackendUnit:
    def test_exception_drops_items_like_a_real_death(self):
        plan = FaultPlan(exception_rate=1.0)
        fb = FaultyBackend(plan, CPUBatchVerifier())
        for pk, m, s in _make_items(3):
            fb.add(pk, m, s)
        assert fb.count() == 3
        with pytest.raises(FaultInjected):
            fb.verify()
        assert fb.count() == 0  # batch dropped, like a dead backend

    def test_corruption_flips_every_verdict(self):
        plan = FaultPlan(corrupt_rate=1.0)
        fb = FaultyBackend(plan, CPUBatchVerifier())
        items = _make_items(4, poison_at=2)
        for pk, m, s in items:
            fb.add(pk, m, s)
        _, mask = fb.verify()
        assert mask == [not b for b in _cpu_mask(items)]

    def test_die_after_counts_dispatches(self):
        plan = FaultPlan(die_after=2)
        name = "test-dieafter"
        cryptobatch.register_backend(
            name, lambda: FaultyBackend(plan, CPUBatchVerifier())
        )
        items = _make_items(2)
        for _ in range(2):  # dispatches 1..2 fine
            bv = new_batch_verifier(name)
            for pk, m, s in items:
                bv.add(pk, m, s)
            ok, _ = bv.verify()
            assert ok
        bv = new_batch_verifier(name)  # dispatch 3 → dead
        for pk, m, s in items:
            bv.add(pk, m, s)
        with pytest.raises(FaultInjected):
            bv.verify()

    def test_plan_from_env(self, monkeypatch):
        monkeypatch.setenv("CBFT_FAULT_EXC_RATE", "0.5")
        monkeypatch.setenv("CBFT_FAULT_DIE_AFTER", "7")
        monkeypatch.setenv("CBFT_FAULT_JITTER_MS", "2.5")
        plan = FaultPlan.from_env()
        assert plan.exception_rate == 0.5
        assert plan.die_after == 7
        assert plan.jitter_ms == 2.5
        plan.clear()
        assert plan.exception_rate == 0.0 and plan.die_after is None


class TestSchedulerIntegration:
    def test_supervised_scheduler_routes_and_flushes_broken(self):
        plan, sup = _faulty(breaker_threshold=1)
        plan.exception_rate = 1.0
        sup.verify_items(_make_items(2))  # trip it
        assert sup.state() == BROKEN
        # flush deadline 10 s out: only the broken short-circuit can
        # release this quickly
        s = VerifyScheduler(spec=sup.spec, flush_us=10_000_000,
                            supervisor=sup)
        s.start()
        try:
            items = _make_items(6, poison_at=2)
            t0 = time.perf_counter()
            ok, mask = s.submit(items).result(timeout=30)
            dt = time.perf_counter() - t0
            assert mask == _cpu_mask(items) and not ok
            assert dt < 5.0, f"broken breaker did not short-circuit ({dt:.1f}s)"
            assert s.metrics.flushes.with_labels(reason="broken").value() >= 1
        finally:
            s.stop()
            sup.stop()

    def test_supervised_scheduler_verdicts_under_faults(self):
        plan, sup = _faulty(breaker_threshold=2, audit_pct=100,
                            audit_sync=True)
        plan.exception_rate = 0.5
        plan.corrupt_rate = 0.3
        s = VerifyScheduler(spec=sup.spec, flush_us=1000, supervisor=sup)
        s.start()
        try:
            for i in range(5):
                items = _make_items(8, tag=bytes([i]),
                                    poison_at=3 if i % 2 else None)
                ok, mask = s.submit(items).result(timeout=30)
                assert mask == _cpu_mask(items)
        finally:
            s.stop()
            sup.stop()

    def test_supervisor_duck_typing(self):
        plan, sup = _faulty()
        assert unwrap_backend(sup) is sup.spec
        assert cryptobatch.backend_name(sup) == sup.spec.name
        bv = new_batch_verifier(sup)
        assert isinstance(bv, SupervisedBatchVerifier)
        items = _make_items(5, poison_at=4)
        for pk, m, s in items:
            bv.add(pk, m, s)
        assert bv.count() == 5
        ok, mask = bv.verify()
        assert not ok and mask == _cpu_mask(items)
        assert bv.verify() == (False, [])
        sup.stop()


class _GatedVerifier(CPUBatchVerifier):
    """verify() blocks until the class gate opens — a controllable
    wedged device plane for backpressure/stop tests."""

    gate = threading.Event()
    entered = threading.Event()

    def verify(self):
        _GatedVerifier.entered.set()
        _GatedVerifier.gate.wait()
        return super().verify()


@pytest.fixture()
def gated_backend():
    _GatedVerifier.gate = threading.Event()
    _GatedVerifier.entered = threading.Event()
    cryptobatch.register_backend("gated", _GatedVerifier)
    yield BackendSpec("gated")
    _GatedVerifier.gate.set()  # release any stragglers


class TestBoundedSubmit:
    def test_backpressure_blocks_then_admits(self, gated_backend):
        s = VerifyScheduler(spec=gated_backend, flush_us=500, max_queue=8)
        assert s.max_queue == 8
        s.start()
        try:
            fut_a = s.submit(_make_items(8, tag=b"a"))  # worker grabs it
            assert _GatedVerifier.entered.wait(5)
            fut_b = s.submit(_make_items(8, tag=b"b"))  # fills the queue
            done = threading.Event()
            box = {}

            def blocked_submit():
                box["fut"] = s.submit(_make_items(4, tag=b"c"))
                done.set()

            t = threading.Thread(target=blocked_submit)
            t.start()
            time.sleep(0.1)
            assert not done.is_set(), "submit should block on a full queue"
            assert s.metrics.backpressure_waits.value() == 1
            _GatedVerifier.gate.set()  # drain the plane
            assert done.wait(10), "submit never unblocked"
            for fut, n in ((fut_a, 8), (fut_b, 8), (box["fut"], 4)):
                ok, mask = fut.result(timeout=10)
                assert ok and len(mask) == n
            assert s.metrics.backpressure_timeouts.value() == 0
        finally:
            _GatedVerifier.gate.set()
            s.stop()

    def test_backpressure_timeout_verifies_inline_on_cpu(
        self, gated_backend, monkeypatch
    ):
        monkeypatch.setenv("CBFT_SUBMIT_TIMEOUT_MS", "200")
        s = VerifyScheduler(spec=gated_backend, flush_us=500, max_queue=8)
        s.start()
        try:
            s.submit(_make_items(8, tag=b"a"))
            assert _GatedVerifier.entered.wait(5)
            s.submit(_make_items(8, tag=b"b"))  # queue now full
            items = _make_items(4, tag=b"c", poison_at=1)
            t0 = time.perf_counter()
            fut = s.submit(items)  # blocks 200 ms, then inline CPU
            dt = time.perf_counter() - t0
            assert fut.done()
            ok, mask = fut.result(timeout=0)
            assert mask == _cpu_mask(items) and not ok
            assert 0.15 <= dt < 5.0
            assert s.metrics.backpressure_timeouts.value() == 1
        finally:
            _GatedVerifier.gate.set()
            s.stop()

    def test_oversize_request_admitted_when_queue_empty(self, gated_backend):
        _GatedVerifier.gate.set()  # plane healthy
        s = VerifyScheduler(spec=gated_backend, flush_us=500, max_queue=4)
        s.start()
        try:
            # 16 > max_queue=4, but the queue is empty: it must pass
            ok, mask = s.submit(_make_items(16)).result(timeout=10)
            assert ok and len(mask) == 16
            assert s.metrics.backpressure_waits.value() == 0
        finally:
            s.stop()

    def test_max_queue_knob_precedence(self, monkeypatch):
        from cometbft_tpu.crypto.scheduler import (
            DEFAULT_MAX_QUEUE,
            max_queue_default,
        )

        monkeypatch.delenv("CBFT_MAX_QUEUE", raising=False)
        assert max_queue_default() == DEFAULT_MAX_QUEUE
        assert max_queue_default(123) == 123
        monkeypatch.setenv("CBFT_MAX_QUEUE", "77")
        assert max_queue_default(123) == 77


class TestStopJoinFailure:
    def test_failed_join_fails_pending_futures(self, gated_backend):
        s = VerifyScheduler(spec=gated_backend, flush_us=500,
                            join_timeout_s=0.2)
        s.start()
        fut_a = s.submit(_make_items(4, tag=b"a"))  # wedges the worker
        assert _GatedVerifier.entered.wait(5)
        fut_b = s.submit(_make_items(4, tag=b"b"))  # left queued
        s.stop()  # join times out after 0.2 s
        for fut in (fut_a, fut_b):
            assert fut.done()
            with pytest.raises(RuntimeError, match="wedged"):
                fut.result(timeout=0)
        # the zombie worker limping home must NOT overwrite the error
        # (first-wins completion)
        _GatedVerifier.gate.set()
        time.sleep(0.3)
        with pytest.raises(RuntimeError, match="wedged"):
            fut_a.result(timeout=0)

    def test_clean_join_still_drains(self, gated_backend):
        _GatedVerifier.gate.set()
        s = VerifyScheduler(spec=gated_backend, flush_us=10_000_000,
                            lane_budget=4096, join_timeout_s=5.0)
        s.start()
        fut = s.submit(_make_items(4))
        s.stop()
        ok, mask = fut.result(timeout=5)
        assert ok and len(mask) == 4


class TestStopMidProbe:
    def test_stop_joins_inflight_probe(self, gated_backend):
        # a warmup canary wedges on the device plane; stop() must join
        # the probe thread (bounded by the dispatch watchdog) instead of
        # leaving a daemon probe to touch the torn-down backend later
        sup = BackendSupervisor(
            spec=gated_backend, dispatch_timeout_ms=300,
            breaker_threshold=3, audit_pct=0,
            probe_base_ms=10, probe_max_ms=80,
        )
        sup.warmup_canary()
        assert _GatedVerifier.entered.wait(5)  # probe is on the device
        t0 = time.monotonic()
        sup.stop()
        # the probe abandons its wedged dispatch at the watchdog bound,
        # so the join is bounded too (well under timeout_s + 5)
        assert time.monotonic() - t0 < 5.0
        assert not any(
            t.name in ("supervisor-probe", "supervisor-canary")
            and t.is_alive()
            for t in threading.enumerate()
        )
        # after stop, probe_now is a no-op that never dispatches
        _GatedVerifier.entered.clear()
        assert sup.probe_now() is False
        assert not _GatedVerifier.entered.is_set()

    def test_stop_idempotent_after_probe_join(self, gated_backend):
        _GatedVerifier.gate.set()
        sup = BackendSupervisor(
            spec=gated_backend, dispatch_timeout_ms=300,
            breaker_threshold=3, audit_pct=0,
            probe_base_ms=10, probe_max_ms=80,
        )
        sup.warmup_canary()
        sup.stop()
        sup.stop()  # second stop must not raise or hang
        assert sup.probe_now() is False


class TestMeshCancellation:
    def test_cancel_scope_installs_and_restores(self):
        from cometbft_tpu.crypto.tpu import mesh

        assert mesh.current_cancel_event() is None
        ev1, ev2 = threading.Event(), threading.Event()
        with mesh.cancel_scope(ev1):
            assert mesh.current_cancel_event() is ev1
            with mesh.cancel_scope(ev2):
                assert mesh.current_cancel_event() is ev2
            assert mesh.current_cancel_event() is ev1
        assert mesh.current_cancel_event() is None

    def test_cancel_scope_is_thread_local(self):
        from cometbft_tpu.crypto.tpu import mesh

        ev = threading.Event()
        seen = {}

        def other():
            seen["ev"] = mesh.current_cancel_event()

        with mesh.cancel_scope(ev):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["ev"] is None

    def test_dispatch_batch_raises_cancelled(self):
        import numpy as np

        from cometbft_tpu.crypto.tpu import mesh

        def packed(start, end):
            return [np.ones(end - start, np.float32)]

        ev = threading.Event()
        ev.set()
        with mesh.cancel_scope(ev):
            with pytest.raises(mesh.DispatchCancelled, match="chunk 0"):
                mesh.dispatch_batch(lambda x: x > 0, packed, 16, 8, 8)

    def test_chunk_errors_carry_chunk_index(self):
        from cometbft_tpu.crypto.tpu import mesh

        def packed(start, end):
            if start >= 8:
                raise ValueError("link died")
            import numpy as np

            return [np.ones(end - start, np.float32)]

        with pytest.raises(RuntimeError, match=r"chunk 1 \(sigs \[8:16\]\)"):
            mesh.dispatch_batch(lambda x: x > 0, packed, 16, 8, 8)

    def test_hang_wakes_on_cancel(self):
        from cometbft_tpu.crypto.faults import _interruptible_hang
        from cometbft_tpu.crypto.tpu import mesh

        ev = threading.Event()
        box = {}

        def run():
            try:
                with mesh.cancel_scope(ev):
                    _interruptible_hang(30.0)
            except mesh.DispatchCancelled:
                box["cancelled"] = True

        t = threading.Thread(target=run)
        t.start()
        time.sleep(0.05)
        ev.set()
        t.join(timeout=5)
        assert not t.is_alive() and box.get("cancelled")


class TestKnobsAndConfig:
    def test_supervisor_knob_precedence(self, monkeypatch):
        for env in ("CBFT_DISPATCH_TIMEOUT_MS", "CBFT_BREAKER_THRESHOLD",
                    "CBFT_AUDIT_PCT"):
            monkeypatch.delenv(env, raising=False)
        assert dispatch_timeout_ms_default() == 60_000
        assert dispatch_timeout_ms_default(5000) == 5000
        assert breaker_threshold_default() == 3
        assert audit_pct_default() == 5
        monkeypatch.setenv("CBFT_DISPATCH_TIMEOUT_MS", "250")
        monkeypatch.setenv("CBFT_BREAKER_THRESHOLD", "9")
        monkeypatch.setenv("CBFT_AUDIT_PCT", "50")
        assert dispatch_timeout_ms_default(5000) == 250
        assert breaker_threshold_default(7) == 9
        assert audit_pct_default(1) == 50

    def test_supervisor_reads_config_values(self):
        sup = BackendSupervisor(
            spec=BackendSpec("tpu"), dispatch_timeout_ms=1234,
            breaker_threshold=5, audit_pct=42,
        )
        assert sup.dispatch_timeout_ms == 1234
        assert sup.breaker_threshold == 5
        assert sup.audit_pct == 42
        sup.stop()

    def test_config_defaults_and_validation(self):
        from cometbft_tpu.config import default_config

        cfg = default_config()
        assert cfg.crypto.dispatch_timeout_ms == 60_000
        assert cfg.crypto.breaker_threshold == 3
        assert cfg.crypto.audit_pct == 5
        assert cfg.crypto.max_queue == 65_536
        cfg.validate_basic()
        cfg.crypto.audit_pct = 0  # off is legal
        cfg.validate_basic()
        for knob, bad in (
            ("dispatch_timeout_ms", 0), ("breaker_threshold", -1),
            ("max_queue", 0), ("audit_pct", 101), ("audit_pct", -1),
        ):
            fresh = default_config()
            setattr(fresh.crypto, knob, bad)
            with pytest.raises(ValueError, match=knob):
                fresh.validate_basic()

    def test_config_toml_round_trip(self, tmp_path):
        from cometbft_tpu.config import (
            default_config,
            load_config_file,
            write_config_file,
        )

        cfg = default_config()
        cfg.crypto.dispatch_timeout_ms = 777
        cfg.crypto.breaker_threshold = 4
        cfg.crypto.audit_pct = 11
        cfg.crypto.max_queue = 2048
        path = str(tmp_path / "config.toml")
        write_config_file(path, cfg)
        loaded = load_config_file(path)
        assert loaded.crypto.dispatch_timeout_ms == 777
        assert loaded.crypto.breaker_threshold == 4
        assert loaded.crypto.audit_pct == 11
        assert loaded.crypto.max_queue == 2048


class TestChaosSoak:
    def test_mini_soak_invariants(self):
        summary = run_chaos_soak(
            n_blocks=8, batch=16, seed=42, dispatch_timeout_ms=300,
            probe_base_ms=15,
        )
        assert summary["wrong_verdicts"] == 0
        assert summary["lost_futures"] == 0
        assert summary["readmitted"] is True
        assert summary["device_resumed_after_recovery"] is True
        assert summary["final_state"] == HEALTHY

    @pytest.mark.slow
    def test_full_soak(self):
        summary = run_chaos_soak(
            n_blocks=40, batch=48, seed=1234, dispatch_timeout_ms=400,
            probe_base_ms=20,
        )
        assert summary["wrong_verdicts"] == 0
        assert summary["lost_futures"] == 0
        assert summary["readmitted"] is True
        assert summary["device_resumed_after_recovery"] is True
        # the schedule must actually have exercised faults
        assert summary["backend_dispatches"] > 0
