"""Adaptive fault-tolerant dispatch: the degradation ladder between
HEALTHY and BROKEN (crypto/supervisor.py, crypto/tpu/mesh.py).

Contract under test:
  - device exceptions are classified transient / oom / persistent by
    scanning the whole exception chain, and only persistents strike the
    breaker on first sight;
  - a transient error is retried once with jittered backoff and a
    successful retry costs no breaker strike and no CPU fallback;
  - an OOM halves the effective mesh chunk cap per retry down to a
    floor, and the cap recovers one doubling per chunk_recover_n
    consecutive clean dispatches (hysteresis);
  - the EWMA latency model hedges an overrunning dispatch with a
    parallel CPU verify, first mask wins, and the loser is audited for
    divergence (divergence trips the breaker);
  - a mixed-verdict batch is triaged: claimed-bad lanes bisected on
    device within the ceil(log2 n) + 1 pass bound, convictions
    CPU-confirmed, offenders attributed per submitting request, and a
    CPU overturn (silent corruption) trips the breaker;
  - the deterministic chaos smoke walks every rung with zero verdict
    divergence (tools/chaos.py runs the same harness).
"""

import math
import threading
import time

import pytest

from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
from cometbft_tpu.crypto.faults import (
    FaultPlan,
    ResourceExhaustedFault,
    TransientFault,
    install,
    run_chaos_smoke,
)
from cometbft_tpu.crypto.supervisor import (
    BROKEN,
    DEGRADED,
    HEALTHY,
    OOM,
    PERSISTENT,
    TRANSIENT,
    BackendSupervisor,
    LatencyModel,
    classify_device_error,
    hedge_pct_default,
    retry_ms_default,
    chunk_recover_n_default,
)
from cometbft_tpu.crypto.tpu import mesh


def _make_items(n, tag=b"", poison_at=None):
    items = []
    for i in range(n):
        k = ed.gen_priv_key_from_secret(tag + bytes([i & 0xFF, i >> 8]))
        msg = b"adaptive-msg-" + tag + i.to_bytes(4, "big")
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


def _total(counter):
    return sum(c.value() for c in counter._series())


_seq = [0]


def _faulty(plan=None, **sup_kwargs):
    _seq[0] += 1
    name = f"test-adaptive-{_seq[0]}"
    plan = install(name=name, inner="cpu",
                   plan=plan if plan is not None else FaultPlan(seed=_seq[0]))
    sup_kwargs.setdefault("dispatch_timeout_ms", 2000)
    sup_kwargs.setdefault("breaker_threshold", 3)
    sup_kwargs.setdefault("audit_pct", 0)
    sup_kwargs.setdefault("probe_base_ms", 10)
    sup_kwargs.setdefault("probe_max_ms", 80)
    sup_kwargs.setdefault("retry_ms", 5)
    sup = BackendSupervisor(spec=BackendSpec(name), **sup_kwargs)
    return plan, sup


@pytest.fixture(autouse=True)
def _clean_chunk_shrink():
    # the shrink level is module state in mesh (it models device memory
    # pressure, which outlives any one supervisor) — isolate tests
    mesh.reset_chunk_shrink()
    yield
    mesh.reset_chunk_shrink()


class TestClassification:
    def test_oom_markers(self):
        for msg in (
            "RESOURCE_EXHAUSTED: while allocating",
            "out of memory on device",
            "HBM allocation failure",
            "oom killed",
        ):
            assert classify_device_error(RuntimeError(msg)) == OOM, msg

    def test_transient_markers(self):
        for msg in (
            "UNAVAILABLE: socket closed",
            "DEADLINE_EXCEEDED waiting for the runtime",
            "connection reset by peer",
            "temporarily unreachable, try again",
        ):
            assert classify_device_error(RuntimeError(msg)) == TRANSIENT, msg

    def test_persistent_default(self):
        assert classify_device_error(RuntimeError("kernel mismatch")) \
            == PERSISTENT

    def test_substring_innocents_stay_persistent(self):
        # "boom" must not trigger the OOM rung (bare-"oom" regression)
        assert classify_device_error(RuntimeError("boom")) == PERSISTENT

    def test_walks_cause_chain(self):
        # mesh.dispatch_batch wraps chunk errors but chains the original
        try:
            try:
                raise RuntimeError("RESOURCE_EXHAUSTED: hbm")
            except RuntimeError as inner:
                raise RuntimeError("chunk 3/8 failed") from inner
        except RuntimeError as outer:
            assert classify_device_error(outer) == OOM

    def test_fault_shapes_classify(self):
        assert classify_device_error(
            TransientFault("UNAVAILABLE: injected")) == TRANSIENT
        assert classify_device_error(
            ResourceExhaustedFault("RESOURCE_EXHAUSTED: injected")) == OOM


class TestLatencyModel:
    def test_cold_returns_none(self):
        assert LatencyModel().predict_p99(1024) is None

    def test_warm_bucket_predicts_tail_above_mean(self):
        lm = LatencyModel()
        for v in (0.010, 0.012, 0.011, 0.013):
            lm.observe(1024, v)
        p99 = lm.predict_p99(1024)
        assert p99 is not None and p99 >= 0.010

    def test_nearest_warm_bucket_fallback(self):
        lm = LatencyModel()
        for _ in range(4):
            lm.observe(1024, 0.010)
        # 4096 bucket is cold: the 1024 one answers for it
        assert lm.predict_p99(4096) == pytest.approx(
            lm.predict_p99(1024))
        # but not for a flush three orders of magnitude away: what the
        # canary's 8 lanes cost says nothing about 10,000
        for _ in range(4):
            lm.observe(8, 0.006)
        assert lm.predict_p99(13) == pytest.approx(lm.predict_p99(8))
        assert lm.predict_p99(1_000_000) is None
        assert LatencyModel.NEIGHBORS == 2

    def test_below_min_samples_stays_cold(self):
        lm = LatencyModel()
        lm.observe(64, 0.001)
        assert lm.predict_p99(64) is None


class TestKnobs:
    def test_defaults_and_env_precedence(self, monkeypatch):
        assert hedge_pct_default() == 200
        assert retry_ms_default() == 25
        assert chunk_recover_n_default() == 32
        monkeypatch.setenv("CBFT_HEDGE_PCT", "350")
        monkeypatch.setenv("CBFT_RETRY_MS", "7")
        monkeypatch.setenv("CBFT_CHUNK_RECOVER_N", "4")
        assert hedge_pct_default(100) == 350  # env beats config
        assert retry_ms_default(100) == 7
        assert chunk_recover_n_default(100) == 4

    def test_config_knobs_validate(self):
        from cometbft_tpu.config import Config

        cfg = Config()
        assert cfg.crypto.hedge_pct == 200
        assert cfg.crypto.retry_ms == 25
        assert cfg.crypto.chunk_recover_n == 32
        cfg.validate_basic()
        cfg.crypto.hedge_pct = 0  # 0 = hedging off, and is valid
        cfg.validate_basic()
        cfg.crypto.hedge_pct = -1
        with pytest.raises(ValueError):
            cfg.validate_basic()
        cfg.crypto.hedge_pct = 200
        cfg.crypto.retry_ms = 0
        with pytest.raises(ValueError):
            cfg.validate_basic()


class TestTransientRetry:
    def test_one_flap_absorbed_without_strike(self):
        plan, sup = _faulty()
        plan.transient_n = 1
        items = _make_items(12, b"flap")
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.state() == HEALTHY  # no strike, no DEGRADED
            assert _total(sup.metrics.retries) == 1
            assert sup.metrics.failures.value() == 0
        finally:
            sup.stop()

    def test_second_flap_in_a_row_falls_through(self):
        # one retry only: two consecutive flaps on the same batch cost a
        # breaker strike + CPU fallback, exactly like before the ladder
        plan, sup = _faulty()
        plan.transient_n = 2
        items = _make_items(12, b"flap2")
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.state() == DEGRADED
            assert sup.metrics.failures.value() == 1
        finally:
            sup.stop()

    def test_persistent_error_not_retried(self):
        plan, sup = _faulty()
        plan.exception_rate = 1.0  # FaultInjected: persistent-shaped
        items = _make_items(12, b"persist")
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.state() == DEGRADED
            assert _total(sup.metrics.retries) == 0
        finally:
            sup.stop()


class TestChunkShrink:
    def test_mesh_shrink_and_floor(self):
        assert mesh.chunk_shrink_levels() == 0
        base = mesh.effective_chunk_cap(8192)
        for lvl in range(1, mesh.MAX_SHRINK_LEVELS + 1):
            assert mesh.shrink_chunk_cap()
            assert mesh.chunk_shrink_levels() == lvl
        assert not mesh.shrink_chunk_cap()  # at the floor
        assert mesh.effective_chunk_cap(8192) == max(
            64, base >> mesh.MAX_SHRINK_LEVELS
        )

    def test_shrunk_cap_respects_min_pad(self):
        for _ in range(mesh.MAX_SHRINK_LEVELS):
            mesh.shrink_chunk_cap()
        assert mesh.effective_chunk_cap(128, min_pad=64) == 64

    def test_recovery_hysteresis_exact_count(self):
        mesh.shrink_chunk_cap()
        mesh.shrink_chunk_cap()
        n = 4
        for _ in range(n - 1):
            assert not mesh.note_clean_dispatch(n)
        assert mesh.note_clean_dispatch(n)  # nth clean recovers a level
        assert mesh.chunk_shrink_levels() == 1
        # the streak resets after a recovery: another n cleans needed
        for _ in range(n - 1):
            assert not mesh.note_clean_dispatch(n)
        assert mesh.note_clean_dispatch(n)
        assert mesh.chunk_shrink_levels() == 0
        # fully recovered: further cleans are no-ops
        assert not mesh.note_clean_dispatch(n)

    def test_shrink_resets_streak(self):
        mesh.shrink_chunk_cap()
        mesh.note_clean_dispatch(3)
        mesh.note_clean_dispatch(3)
        mesh.shrink_chunk_cap()  # a fresh OOM voids the progress
        assert not mesh.note_clean_dispatch(3)
        assert not mesh.note_clean_dispatch(3)
        assert mesh.note_clean_dispatch(3)

    def test_oom_dispatch_shrinks_to_floor_then_cpu(self):
        plan, sup = _faulty(chunk_recover_n=2)
        plan.oom_rate = 1.0
        items = _make_items(12, b"oom")
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            # every retry shrank one level until the floor, then the
            # failure fell through to one breaker strike + CPU
            assert mesh.chunk_shrink_levels() == mesh.MAX_SHRINK_LEVELS
            assert sup.metrics.chunk_shrinks.value() \
                == mesh.MAX_SHRINK_LEVELS
            assert _total(sup.metrics.retries) == mesh.MAX_SHRINK_LEVELS
            assert sup.state() == DEGRADED
            # repair: clean dispatches recover one doubling per
            # chunk_recover_n (supervisor default threaded from knob)
            plan.clear()
            for _ in range(2 * sup.chunk_recover_n):
                assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.chunk_recoveries.value() == 2
            assert mesh.chunk_shrink_levels() == mesh.MAX_SHRINK_LEVELS - 2
            assert sup.state() == HEALTHY
        finally:
            sup.stop()


class TestHedge:
    def _primed(self, items, **kwargs):
        plan, sup = _faulty(**kwargs)
        for _ in range(5):
            sup.latency_model.observe(len(items), 0.002)
        return plan, sup

    def test_overrunning_dispatch_hedges_and_agrees(self):
        items = _make_items(12, b"hedge")
        plan, sup = self._primed(items)
        plan.hang_rate = 1.0
        plan.hang_s = 0.04  # well past predicted p99 x 2, under watchdog
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.hedge_fires.value() == 1
            assert _total(sup.metrics.hedge_wins) == 1
            # let the loser limp home and be compared against the winner
            time.sleep(plan.hang_s + 0.02)
            assert sup.metrics.hedge_divergence.value() == 0
            assert sup.state() in (HEALTHY, DEGRADED)
        finally:
            sup.stop()

    def test_hedge_disabled_by_zero_pct(self):
        items = _make_items(12, b"nohedge")
        plan, sup = self._primed(items, hedge_pct=0)
        plan.hang_rate = 1.0
        plan.hang_s = 0.04
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.hedge_fires.value() == 0
        finally:
            sup.stop()

    def test_cold_model_never_hedges(self):
        plan, sup = _faulty()
        plan.hang_rate = 1.0
        plan.hang_s = 0.04
        items = _make_items(12, b"cold")
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.hedge_fires.value() == 0
        finally:
            sup.stop()

    def test_loser_divergence_trips_breaker(self):
        # device hangs past the hedge point AND returns corrupt verdicts:
        # the CPU mask is released (ground truth), and when the device
        # limps home disagreeing, the audit path breaks the circuit
        items = _make_items(12, b"hedge-corrupt")
        plan, sup = self._primed(items)
        plan.hang_rate = 1.0
        plan.hang_s = 0.04
        plan.corrupt_rate = 1.0
        try:
            mask = sup.verify_items(items)
            assert mask == _cpu_mask(items)  # corruption never released
            deadline = time.monotonic() + 2
            while time.monotonic() < deadline and sup.state() != BROKEN:
                time.sleep(0.005)
            assert sup.state() == BROKEN
            assert sup.metrics.hedge_divergence.value() == 1
        finally:
            sup.stop()

    def test_hedge_threshold_beyond_watchdog_stays_plain(self):
        # predicted hedge point past dispatch_timeout_ms: plain watchdog
        items = _make_items(12, b"far")
        plan, sup = _faulty(dispatch_timeout_ms=50)
        for _ in range(5):
            sup.latency_model.observe(len(items), 10.0)  # absurd p99
        plan.hang_rate = 1.0
        plan.hang_s = 5.0
        try:
            assert sup.verify_items(items) == _cpu_mask(items)
            assert sup.metrics.hedge_fires.value() == 0
            assert sup.metrics.watchdog_kills.value() == 1
            assert sup.state() == BROKEN
        finally:
            sup.stop()


class _LyingVerifier(CPUBatchVerifier):
    """CPU verifier that falsely claims configured lanes bad — but only
    on dispatches of at least ``full_n`` items, so triage's smaller
    re-dispatches see the truth (a transient device glitch)."""

    lie_lanes = ()
    full_n = 0
    persistent = False

    def verify(self):
        n = self.count()
        ok, mask = super().verify()
        if self.persistent or n >= type(self).full_n:
            mask = list(mask)
            for lane in type(self).lie_lanes:
                if lane < n:
                    mask[lane] = False
            ok = all(mask)
        return ok, mask


class TestTriage:
    def _lying(self, lanes, full_n, persistent=False):
        _seq[0] += 1
        name = f"test-liar-{_seq[0]}"
        _LyingVerifier.lie_lanes = tuple(lanes)
        _LyingVerifier.full_n = full_n
        _LyingVerifier.persistent = persistent
        cryptobatch.register_backend(name, _LyingVerifier)
        return BackendSupervisor(
            spec=BackendSpec(name), dispatch_timeout_ms=2000,
            breaker_threshold=3, audit_pct=0, probe_base_ms=10,
            probe_max_ms=80, retry_ms=5,
        )

    def test_genuinely_bad_lanes_convicted_and_attributed(self):
        plan, sup = _faulty()
        items = _make_items(24, b"triage", poison_at=7)
        truth = _cpu_mask(items)
        try:
            before = sup.metrics.device_dispatches.value()
            mask = sup.verify_items(
                items, reason="flush",
                origins=[(8, "consensus", 5), (8, "blocksync", 6),
                         (8, "evidence", 7)],
            )
            assert mask == truth
            passes = sup.metrics.triage_passes.value()
            assert 1 <= passes <= math.ceil(math.log2(24)) + 1
            # device passes observed via the dispatch counter too
            assert sup.metrics.device_dispatches.value() - before \
                == 1 + passes
            offenders = {
                c._labels["subsystem"]: c.value()
                for c in sup.metrics.triage_offenders._series()
                if "subsystem" in c._labels
            }
            assert offenders == {"consensus": 1.0}  # lane 7 = request 1
            assert sup.metrics.triage_divergence.value() == 0
            assert sup.state() == HEALTHY  # a bad signature is not a
            # device incident: the breaker must not move
        finally:
            sup.stop()

    def test_transient_device_lie_cleared_on_reaffirm(self):
        # the device wrongly claims lanes bad once; triage's re-dispatch
        # sees them clean and clears them without any CPU confirmation
        sup = self._lying(lanes=(3, 11), full_n=16)
        items = _make_items(16, b"lie")
        try:
            mask = sup.verify_items(items)
            assert mask == [True] * 16
            assert sup.metrics.triage_runs.value() == 1
            assert sup.metrics.triage_divergence.value() == 0
            assert sup.state() == HEALTHY
        finally:
            sup.stop()

    def test_persistent_device_lie_is_silent_corruption(self):
        # the device insists lane 0 is bad through every bisection pass
        # (lane 0 so the lie survives re-indexed re-dispatches): the CPU
        # ground truth overturns the conviction, the released mask is
        # correct, and the breaker opens (audit cause)
        sup = self._lying(lanes=(0,), full_n=16, persistent=True)
        items = _make_items(16, b"liar")
        try:
            mask = sup.verify_items(items)
            assert mask == [True] * 16  # CPU verdict wins, always
            assert sup.metrics.triage_divergence.value() == 1
            assert sup.state() == BROKEN
            assert sup.metrics.trips.with_labels(
                cause="audit").value() >= 1
        finally:
            sup.stop()

    def test_pass_bound_8k_batch_8_offenders(self):
        plan, sup = _faulty()
        n = 2048  # same shape as the bench's 8k assert, CI-sized
        items = _make_items(n, b"big")
        for lane in range(0, n, n // 8):
            pk, m, _ = items[lane]
            items[lane] = (pk, m, b"\x21" * 64)
        truth = _cpu_mask(items)
        try:
            before = sup.metrics.device_dispatches.value()
            mask = sup.verify_items(items)
            assert mask == truth
            passes = sup.metrics.device_dispatches.value() - before - 1
            assert passes <= math.ceil(math.log2(n)) + 1
        finally:
            sup.stop()

    def test_half_bad_batch_exact_verdicts_and_attribution(self):
        # PR 18 edge: a 50% byzantine flood, invalid lanes interleaved
        # with honest ones — worst case for run-coalescing (every
        # suspect segment is a singleton). Verdicts stay lane-exact,
        # attribution splits exactly across the contributing
        # subsystems, and the breaker never moves for signature crime.
        plan, sup = _faulty()
        n = 64
        items = _make_items(n, b"half")
        for lane in range(1, n, 2):
            pk, m, s = items[lane]
            items[lane] = (pk, m, bytes(s[:-1]) + bytes([s[-1] ^ 1]))
        truth = _cpu_mask(items)
        assert truth.count(False) == n // 2
        try:
            before = sup.metrics.device_dispatches.value()
            mask = sup.verify_items(
                items, reason="flush",
                origins=[(n // 2, "consensus", 9),
                         (n // 2, "blocksync", 9)],
            )
            assert mask == truth
            passes = sup.metrics.device_dispatches.value() - before - 1
            assert 1 <= passes <= math.ceil(math.log2(n)) + 1
            offenders = {
                c._labels["subsystem"]: c.value()
                for c in sup.metrics.triage_offenders._series()
                if "subsystem" in c._labels
            }
            assert offenders == {"consensus": 16.0, "blocksync": 16.0}
            assert sup.metrics.triage_divergence.value() == 0
            assert sup.state() == HEALTHY
        finally:
            sup.stop()

    def test_all_byzantine_flush_convicts_every_lane(self):
        # PR 18 edge: 100% of the flush is invalid — one maximal
        # suspect segment spanning the whole batch. Every lane
        # convicts, the full flush is charged to its origin, the pass
        # bound holds, and no conviction is overturned (so no breaker
        # trip: a byzantine committee is not a device incident).
        plan, sup = _faulty()
        n = 32
        items = _make_items(n, b"allbad")
        for lane in range(n):
            pk, m, s = items[lane]
            items[lane] = (pk, m, bytes(s[:-1]) + bytes([s[-1] ^ 1]))
        try:
            before = sup.metrics.device_dispatches.value()
            mask = sup.verify_items(
                items, reason="flush", origins=[(n, "consensus", 3)],
            )
            assert mask == [False] * n
            passes = sup.metrics.device_dispatches.value() - before - 1
            assert 1 <= passes <= math.ceil(math.log2(n)) + 1
            offenders = {
                c._labels["subsystem"]: c.value()
                for c in sup.metrics.triage_offenders._series()
                if "subsystem" in c._labels
            }
            assert offenders == {"consensus": float(n)}
            assert sup.metrics.triage_divergence.value() == 0
            assert sup.state() == HEALTHY
        finally:
            sup.stop()

    def test_triage_device_death_falls_back_to_cpu(self):
        # the device dies mid-triage: remaining suspects go to the CPU
        # ground truth, verdicts stay exact, no breaker strike for it
        plan, sup = _faulty()
        items = _make_items(16, b"die", poison_at=4)
        truth = _cpu_mask(items)
        plan.die_after = 1  # first dispatch fine, triage passes raise
        try:
            assert sup.verify_items(items) == truth
            assert sup.metrics.triage_cpu_fallbacks.value() == 1
        finally:
            sup.stop()


class TestSchedulerOriginsThreading:
    def test_origins_reach_supervisor(self):
        from cometbft_tpu.crypto.scheduler import VerifyScheduler

        calls = []

        class Spy:
            spec = BackendSpec("cpu")

            @staticmethod
            def state():
                return HEALTHY

            @staticmethod
            def verify_items(items, reason="direct", origins=None):
                calls.append(origins)
                return _cpu_mask(items)

        sched = VerifyScheduler(spec=BackendSpec("cpu"), supervisor=Spy())
        a, b = _make_items(3, b"oa"), _make_items(2, b"ob")
        fa = sched.submit(a, subsystem="consensus", height=42)
        fb = sched.submit(b, subsystem="evidence")
        ok_a, mask_a = fa.result(timeout=5)
        ok_b, _ = fb.result(timeout=5)
        assert ok_a and ok_b and mask_a == [True, True, True]
        # not-running scheduler dispatches inline, one request per call
        assert calls == [
            [(3, "consensus", 42)],
            [(2, "evidence", None)],
        ]


class TestChaosSmoke:
    def test_every_rung_walked_no_divergence(self):
        s = run_chaos_smoke(seed=23)
        assert s["wrong_verdicts"] == 0
        assert s["retries"] >= 1
        assert s["state_after_transient"] == HEALTHY
        assert s["chunk_shrinks"] >= 1
        assert s["shrink_levels_peak"] == mesh.MAX_SHRINK_LEVELS
        assert s["chunk_recoveries"] >= 1
        assert s["hedge_fires"] >= 1
        assert s["hedge_wins"] >= 1
        assert s["hedge_divergence"] == 0
        assert s["triage_runs"] >= 1
        assert s["triage_passes"] >= 1
        assert s["triage_offenders"] == {"blocksync": 1.0}
        assert s["triage_clean_futures_ok"]
        assert not s["triage_tripped_breaker"]
        assert s["triage_divergence"] == 0
        assert s["state_broken"] == BROKEN
        assert s["probe_ok"]
        assert s["state_final"] == HEALTHY
