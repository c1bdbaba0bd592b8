"""Decision plane: routing-decision ledger, prediction accuracy, and
the anomaly watchdog (crypto/decisions.py + the scheduler/supervisor
feeders).

Contract under test:

  - every coalesced flush through VerifyScheduler._verify lands exactly
    ONE RouteDecision whose taken route is the same label _note_route
    counted, so ledger counts reconcile with queue_snapshot()['routes']
    to the unit — including when the dispatch raises or falls back;
  - a supervised sharded dispatch that falls back (quarantined mesh)
    still produces exactly one record: taken='sharded', final='single',
    the fallback event attributed, the ORIGINAL candidate prices kept;
  - prediction ladder: the ledger's own per-(route, bucket) wall EWMA
    once >= MIN_SELF_OBS observations, then the wire CostProfile, then
    None (cold decisions record no error);
  - APE is normalized by the PREDICTION (a world slower than the model
    claims reads unbounded, not saturated below 1.0), and the watchdog
    trips hysteretically: >= MIN_TRIP_OBS windowed observations, one
    on_anomaly fire per episode, re-arm only after REARM_CLEAN clean
    samples below half the trip level;
  - the time-series ring is bounded at RING_CAPACITY and samples on the
    finish path (lazy clock-compare — no background thread);
  - the chaos staleness rung (crypto/faults.py run_chaos_stale_model /
    tools/chaos.py --stale-model) passes end to end: injected jitter
    trips the watchdog, fires exactly one incident dump, re-arms.

Runs CPU-only — no device plane required.
"""

import pytest

from cometbft_tpu.crypto import decisions as declib
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.decisions import (
    MIN_SELF_OBS,
    MIN_TRIP_OBS,
    REARM_CLEAN,
    RING_CAPACITY,
    DecisionLedger,
    RouteDecision,
)
from cometbft_tpu.crypto.scheduler import VerifyScheduler


def _make_items(n, tag=b"dec"):
    items = []
    for i in range(n):
        k = ed.gen_priv_key_from_secret(tag + bytes([i & 0xFF, i >> 8]))
        msg = b"decision-msg-" + i.to_bytes(4, "big")
        items.append((k.pub_key(), msg, k.sign(msg)))
    return items


@pytest.fixture(autouse=True)
def _no_default_ledger():
    """Tests install their own ledger; never leak one into the suite."""
    prev = declib.set_default_ledger(None)
    yield
    declib.set_default_ledger(prev)


class _StubProfile:
    """CostProfile stand-in with fixed per-route prices."""

    def __init__(self, prices):
        self.prices = prices

    def predict_ms(self, route, bucket):
        return self.prices.get(route)


# ---------------------------------------------------------------------------
# record + ledger core
# ---------------------------------------------------------------------------


class TestRouteDecisionRecord:
    def test_as_dict_final_defaults_to_taken(self):
        dec = RouteDecision(
            seq=1, n=17, reason="size", capacity=0.5, breakers=None,
            keystore=None, qos=None, predicted={"cpu": 1.0},
        )
        dec.taken = "cpu"
        d = dec.as_dict()
        assert d["bucket"] == 32
        assert d["final"] == "cpu" and d["diverted"] is False

    def test_diverted_when_final_differs(self):
        dec = RouteDecision(
            seq=1, n=4, reason="size", capacity=None, breakers=None,
            keystore=None, qos=None, predicted={},
        )
        dec.taken = "sharded"
        dec.final = "single"
        assert dec.diverted is True
        assert dec.as_dict()["final"] == "single"


class TestLedgerCore:
    def test_candidates_always_price_all_three_rungs(self):
        led = DecisionLedger(cost_profile=_StubProfile({"single": 3.0}))
        dec = led.open(n=10, reason="size")
        assert set(dec.predicted) == {"cpu", "single", "sharded"}
        assert dec.predicted["single"] == 3.0
        assert dec.predicted["cpu"] is None

    def test_sub_routes_priced_only_when_known(self):
        led = DecisionLedger(
            cost_profile=_StubProfile({"single": 3.0, "indexed": 2.0})
        )
        dec = led.open(n=10, reason="size")
        assert dec.predicted["indexed"] == 2.0
        cold = DecisionLedger(cost_profile=_StubProfile({"single": 3.0}))
        assert "indexed" not in cold.open(n=10, reason="size").predicted

    def test_self_ewma_outranks_wire_profile_once_warm(self):
        led = DecisionLedger(cost_profile=_StubProfile({"cpu": 100.0}))
        assert led.predict_ms("cpu", 16) == 100.0
        for _ in range(MIN_SELF_OBS):
            dec = led.open(n=16, reason="size")
            dec.taken = "cpu"
            led.finish(dec, 0.002)
        pred = led.predict_ms("cpu", 16)
        assert pred == pytest.approx(2.0, rel=0.05)

    def test_cold_decision_records_no_error(self):
        led = DecisionLedger()
        dec = led.open(n=8, reason="size")
        dec.taken = "cpu"
        led.finish(dec, 0.001)
        assert dec.error_ms is None
        assert led.snapshot()["windowed"]["observations"] == 0

    def test_regret_is_taken_minus_best_candidate(self):
        led = DecisionLedger(
            cost_profile=_StubProfile({"cpu": 10.0, "single": 2.0})
        )
        dec = led.open(n=16, reason="size")
        dec.taken = "cpu"
        led.finish(dec, 0.010)
        assert dec.regret_ms == pytest.approx(8.0)
        win = led.snapshot()["windowed"]
        assert win["regret_ms"] == pytest.approx(8.0)
        assert win["regret_rate"] == 1.0  # 8ms > 10% of the 10ms claim

    def test_ape_normalized_by_prediction_not_wall(self):
        # a 2ms claim measured at 10ms must read APE 4.0 (unbounded
        # regime), NOT |10-2|/10 = 0.8 (saturating regime)
        led = DecisionLedger(cost_profile=_StubProfile({"cpu": 2.0}))
        dec = led.open(n=16, reason="size")
        dec.taken = "cpu"
        led.finish(dec, 0.010)
        assert dec.error_ms == pytest.approx(8.0)
        assert led.snapshot()["windowed"]["mape"] == pytest.approx(4.0)

    def test_diverted_wall_never_folds_into_taken_profile(self):
        led = DecisionLedger(cost_profile=_StubProfile({"sharded": 2.0}))
        dec = led.open(n=16, reason="size")
        dec.taken = "sharded"
        led.note_event(dec, "sharded_fallback", final="single")
        led.finish(dec, 0.500)  # includes the failed sharded attempt
        snap = led.snapshot()
        assert snap["fallbacks"] == {"sharded": 1}
        st = [p for p in snap["profiles"] if p["route"] == "sharded"]
        assert st and st[0]["n"] == 0  # no wall folded
        assert dec.error_ms is None


# ---------------------------------------------------------------------------
# scheduler feed + reconciliation
# ---------------------------------------------------------------------------


class TestSchedulerFeed:
    def test_one_decision_per_flush_reconciles_with_routes(self):
        led = DecisionLedger()
        declib.set_default_ledger(led)
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
        sched.start()
        try:
            for _ in range(5):
                ok, mask = sched.submit(
                    _make_items(8), subsystem="test"
                ).result(timeout=60)
                assert ok and all(mask)
            routes = sched.queue_snapshot()["routes"]
        finally:
            sched.stop()
        counts = led.counts()
        assert sum(counts.values()) == sum(routes.values()) > 0
        for route in set(counts) | set(routes):
            assert counts.get(route, 0) == routes.get(route, 0)

    def test_decision_carries_flush_inputs(self):
        led = DecisionLedger()
        declib.set_default_ledger(led)
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
        sched.start()
        try:
            sched.submit(
                _make_items(6), subsystem="consensus", height=42
            ).result(timeout=60)
        finally:
            sched.stop()
        rec = led.snapshot()["recent"][-1]
        assert rec["n"] == 6 and rec["bucket"] == 8
        assert rec["taken"] == "cpu" and rec["diverted"] is False
        assert rec["wall_ms"] > 0.0
        assert set(rec["predicted_ms"]) >= {"cpu", "single", "sharded"}

    def test_no_ledger_installed_costs_nothing_and_verifies(self):
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
        sched.start()
        try:
            ok, mask = sched.submit(_make_items(4)).result(timeout=60)
        finally:
            sched.stop()
        assert ok and all(mask)

    def test_unsupervised_backend_death_is_one_cpu_fallback_record(self):
        # the backend raises on construction -> scheduler CPU ground
        # truth; the record must show the divergence, not a second row
        led = DecisionLedger()
        declib.set_default_ledger(led)
        sched = VerifyScheduler(
            spec=BackendSpec("no-such-backend"), flush_us=300
        )
        sched.start()
        try:
            ok, mask = sched.submit(_make_items(4)).result(timeout=60)
            routes = sched.queue_snapshot()["routes"]
        finally:
            sched.stop()
        assert ok and all(mask)
        counts = led.counts()
        assert counts == {"single": 1}          # the taken label
        assert routes["single"] == 1            # reconciles to the unit
        rec = led.snapshot()["recent"][-1]
        assert rec["final"] == "cpu" and rec["diverted"] is True
        assert "cpu_fallback" in rec["events"]


class TestShardedFallbackDecision:
    def test_quarantined_mesh_fallback_is_one_record(self, monkeypatch):
        # satellite 4: a sharded dispatch that falls back must produce
        # exactly one decision record carrying the final route AND the
        # original candidate set
        from cometbft_tpu.crypto.faults import FaultPlan, install
        from cometbft_tpu.crypto.supervisor import BackendSupervisor
        from cometbft_tpu.crypto.tpu import topology

        name = "dec-sharded-fb"
        install(name=name, inner="cpu", plan=FaultPlan(seed=3))
        topo = topology.DeviceTopology.virtual(2)
        topo.set_quarantined(1)  # one healthy domain: sharded must fall back
        before = topology.default_topology()
        sup = BackendSupervisor(
            spec=BackendSpec(name), topology=topo,
            dispatch_timeout_ms=60_000, hedge_pct=0, audit_pct=0,
            probe_base_ms=60_000, probe_max_ms=120_000,
        )
        led = DecisionLedger()
        declib.set_default_ledger(led)
        monkeypatch.setenv("CBFT_MESH_ROUTE", "sharded")
        sched = VerifyScheduler(
            spec=BackendSpec(name), supervisor=sup, flush_us=300,
        )
        sched.start()
        try:
            ok, mask = sched.submit(
                _make_items(32, tag=b"fb"), subsystem="test"
            ).result(timeout=60)
            routes = sched.queue_snapshot()["routes"]
        finally:
            sched.stop()
            sup.stop()
            topology.set_default_topology(before)
        assert ok and all(mask)
        counts = led.counts()
        assert counts == {"sharded": 1}
        assert routes["sharded"] == 1  # reconciles with the counter
        recent = led.snapshot()["recent"]
        assert len(recent) == 1  # exactly one record for the flush
        rec = recent[0]
        assert rec["taken"] == "sharded"
        assert rec["final"] == "single" and rec["diverted"] is True
        assert "sharded_fallback" in rec["events"]
        # the ORIGINAL candidates survive on the record
        assert set(rec["predicted_ms"]) >= {"cpu", "single", "sharded"}


# ---------------------------------------------------------------------------
# watchdog + ring
# ---------------------------------------------------------------------------


def _feed(led, wall_ms, n=1, route="cpu", bucket_n=16):
    for _ in range(n):
        dec = led.open(n=bucket_n, reason="size")
        dec.taken = route
        led.finish(dec, wall_ms / 1e3)


class TestAnomalyWatchdog:
    def test_hysteretic_trip_fire_once_and_rearm(self):
        fires = []
        led = DecisionLedger(
            window=MIN_TRIP_OBS,
            ring_interval_s=0.0,  # evaluate on every finish
            on_anomaly=lambda cause, value: fires.append((cause, value)),
        )
        _feed(led, 2.0, n=MIN_TRIP_OBS + MIN_SELF_OBS)  # converge clean
        assert led.watchdog_state()["tripped"] is None
        _feed(led, 50.0, n=4)  # stale world: APE (50-2)/2 = 24 >> trip
        wd = led.watchdog_state()
        assert wd["tripped"] == "mape" and wd["trips"] == 1
        assert len(fires) == 1 and fires[0][0] == "mape"
        # staying hot never re-fires the episode
        _feed(led, 50.0, n=4)
        assert led.watchdog_state()["trips"] == 1 and len(fires) == 1
        # recovery: walls return to the (now adapted) prediction; the
        # window drains below half the trip, REARM_CLEAN samples re-arm
        pred = led.predict_ms("cpu", 16)
        _feed(led, pred, n=led.window + REARM_CLEAN)
        wd = led.watchdog_state()
        assert wd["tripped"] is None and wd["trips"] == 1
        # a second stale regime is a second episode with its own fire
        _feed(led, pred * 40.0, n=2)
        assert led.watchdog_state()["trips"] == 2 and len(fires) == 2

    def test_no_trip_below_min_observations(self):
        fires = []
        led = DecisionLedger(
            window=MIN_TRIP_OBS,
            ring_interval_s=0.0,
            cost_profile=_StubProfile({"cpu": 1.0}),
            on_anomaly=lambda *a: fires.append(a),
        )
        # wildly wrong predictions, but fewer than MIN_TRIP_OBS of them
        _feed(led, 100.0, n=MIN_TRIP_OBS - 1)
        assert led.watchdog_state()["tripped"] is None
        assert not fires

    def test_regret_rate_trips_on_its_own_axis(self):
        fires = []
        led = DecisionLedger(
            window=MIN_TRIP_OBS,
            ring_interval_s=0.0,
            # cpu claims 10ms, single claims 1ms: taking cpu every time
            # is a 9ms regret event per decision (rate 1.0 > 0.5), while
            # APE stays 0 (wall == claim) so only regret can trip
            cost_profile=_StubProfile({"cpu": 10.0, "single": 1.0}),
            on_anomaly=lambda cause, value: fires.append(cause),
        )
        _feed(led, 10.0, n=MIN_TRIP_OBS)
        wd = led.watchdog_state()
        assert wd["tripped"] == "regret"
        assert fires == ["regret"]

    def test_on_anomaly_exception_never_escapes(self):
        def boom(cause, value):
            raise RuntimeError("capture path died")

        led = DecisionLedger(
            window=MIN_TRIP_OBS, ring_interval_s=0.0, on_anomaly=boom,
        )
        _feed(led, 2.0, n=MIN_TRIP_OBS + MIN_SELF_OBS)
        _feed(led, 80.0, n=2)  # fires boom through the trip path
        assert led.watchdog_state()["trips"] == 1


class TestTimeSeriesRing:
    def test_ring_samples_on_finish_and_is_bounded(self):
        led = DecisionLedger(ring_interval_s=0.0)
        _feed(led, 2.0, n=RING_CAPACITY + 20)
        ring = led.snapshot()["ring"]
        assert len(ring) == RING_CAPACITY
        s = ring[-1]
        assert {
            "ts", "duty_cycle", "p99_ms", "burn_rate", "mape",
            "regret_rate", "regret_ms",
        } <= set(s)

    def test_interval_gates_sampling(self):
        t = [0.0]
        led = DecisionLedger(ring_interval_s=10.0, clock=lambda: t[0])
        _feed(led, 2.0, n=5)  # all at t=0: only the first passes the gate
        assert len(led.snapshot()["ring"]) == 1
        t[0] = 11.0
        _feed(led, 2.0, n=1)
        assert len(led.snapshot()["ring"]) == 2

    def test_snapshot_is_json_clean(self):
        import json

        led = DecisionLedger(ring_interval_s=0.0)
        _feed(led, 2.0, n=5)
        json.dumps(led.snapshot())  # /debug/verify must serialize it


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------


class TestKnobs:
    def test_env_overrides_win(self, monkeypatch):
        monkeypatch.setenv("CBFT_DECISION_LEDGER", "off")
        assert declib.decision_ledger_default(True) is False
        monkeypatch.setenv("CBFT_DECISION_WINDOW", "128")
        assert declib.decision_window_default(32) == 128
        monkeypatch.setenv("CBFT_DECISION_MAPE_TRIP", "3.5")
        assert declib.decision_mape_trip_default(1.0) == 3.5

    def test_config_values_flow_through(self, monkeypatch):
        monkeypatch.delenv("CBFT_DECISION_LEDGER", raising=False)
        monkeypatch.delenv("CBFT_DECISION_WINDOW", raising=False)
        monkeypatch.delenv("CBFT_DECISION_MAPE_TRIP", raising=False)
        assert declib.decision_ledger_default(False) is False
        assert declib.decision_window_default(32) == 32
        assert declib.decision_mape_trip_default(1.5) == 1.5
        assert declib.decision_window_default(None) == declib.DEFAULT_WINDOW

    def test_malformed_env_falls_back(self, monkeypatch):
        monkeypatch.setenv("CBFT_DECISION_WINDOW", "not-a-number")
        assert declib.decision_window_default(None) == declib.DEFAULT_WINDOW
        monkeypatch.setenv("CBFT_DECISION_MAPE_TRIP", "-2")
        assert (
            declib.decision_mape_trip_default(None)
            == declib.DEFAULT_MAPE_TRIP
        )


# ---------------------------------------------------------------------------
# bench history direction rules (satellite 5b)
# ---------------------------------------------------------------------------


class TestBenchHistoryDecisionDirection:
    @staticmethod
    def _load():
        import importlib.util
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench_history_decisions_test",
            os.path.join(repo, "tools", "bench_history.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_decision_quality_leaves_are_lower_is_better(self):
        bh = self._load()
        for leaf in ("decisions_worst_mape", "decisions_regret_ms",
                     "stages.decisions.decisions_worst_mape",
                     "verify_route_mape"):
            assert bh.direction(leaf) == bh.LOWER_IS_BETTER, leaf
        # booleans / counts stay directionless
        assert bh.direction("profiles_scored") is None


# ---------------------------------------------------------------------------
# chaos staleness rung
# ---------------------------------------------------------------------------


class TestChaosStaleModelRung:
    def test_jitter_trips_watchdog_once_and_rearms(self):
        from cometbft_tpu.crypto.faults import run_chaos_stale_model

        summary = run_chaos_stale_model(seed=11)
        assert summary["ok"] is True
        assert summary["wrong_verdicts"] == 0
        assert summary["trips"] == 1
        assert summary["anomaly_fires"] == 1
        assert summary["incident_dumps"] == 1
        assert summary["rearmed"] is True
        assert summary["trip_cause"] in ("mape", "regret")
        # ISSUE 16: the trip must also roll the PRICED live router back
        # to thresholds exactly once, and recovery must re-admit it
        assert summary["router_rollbacks"] == 1
        assert summary["router_readmits"] == 1
        assert summary["router_live"] == "priced"
        assert summary["router_priced_records"] > 0
