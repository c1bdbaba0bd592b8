"""Live priced router (ISSUE 16) — the argmin must agree with the
threshold ladder when warm, fall back to it when cold, roll back to it
when the decision plane's watchdog says the cost model is lying, and
pick the indexed steady-state wire exactly when the flush's keys are
resident. CBFT_MESH_ROUTE pins beat every router; a malformed pin is
parsed once, warned once, and then ignored.

Runs on the virtual CPU mesh (conftest.py); the indexed tests pin
n_devices to 1 the same way tests/test_keystore.py does.
"""

import hashlib
import json
from types import SimpleNamespace

import pytest

from cometbft_tpu.crypto import decisions as declib
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.scheduler import (
    ROUTER_REARM_CLEAN,
    VerifyScheduler,
    router_default,
)
from cometbft_tpu.crypto.tpu import ed25519_batch as eb
from cometbft_tpu.crypto.tpu import keystore, mesh, topology
from tools import route_audit


# Per-route seed menus: the third prediction rung, so the argmin is
# fully priced without walking a single route first.
def _seed(menu):
    return lambda route, bucket: menu.get(route)


# single cheapest everywhere — priced and threshold must then agree on
# every unsupervised flush size
_SINGLE_CHEAP = {"cpu": 50.0, "single": 1.0, "sharded": 40.0}


class _Log:
    def __init__(self):
        self.errors = []
        self.infos = []

    def error(self, msg, **kw):
        self.errors.append((msg, kw))

    def info(self, msg, **kw):
        self.infos.append((msg, kw))

    def debug(self, msg, **kw):
        pass

    def warning(self, msg, **kw):
        pass


def _sched(router="priced", supervisor=None, logger=None, spec="faux"):
    return VerifyScheduler(
        spec=BackendSpec(spec), router=router, supervisor=supervisor,
        logger=logger,
    )


@pytest.fixture
def ledger():
    """A seeded decision ledger installed as the process default (the
    priced router reads declib.default_ledger()), restored after."""
    led = declib.DecisionLedger(
        window=8, ring_interval_s=1e9, seed=_seed(_SINGLE_CHEAP)
    )
    prev = declib.set_default_ledger(led)
    yield led
    declib.set_default_ledger(prev)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    monkeypatch.delenv("CBFT_ROUTER", raising=False)
    monkeypatch.delenv("CBFT_MESH_ROUTE", raising=False)


def _routed(sched, led, n, items=()):
    """One routing decision exactly as _verify would make it: open a
    priced record with the scheduler's own feasibility, park it as the
    flush thread's current decision, route."""
    items = list(items)
    feas = sched._decision_feasible(items, sched._decision_breakers())
    dec = led.open(n, "test", feasible=feas)
    with declib.use(dec):
        return sched._route(n, items)


class TestRouterKnob:
    def test_default_is_priced(self):
        assert router_default() == "priced"
        assert router_default(None) == "priced"

    def test_config_value_respected(self):
        assert router_default("threshold") == "threshold"
        assert router_default("priced") == "priced"

    def test_env_beats_config(self, monkeypatch):
        monkeypatch.setenv("CBFT_ROUTER", "threshold")
        assert router_default("priced") == "threshold"
        monkeypatch.setenv("CBFT_ROUTER", "priced")
        assert router_default("threshold") == "priced"

    def test_unrecognized_degrades_to_threshold(self, monkeypatch):
        assert router_default("bogus") == "threshold"
        monkeypatch.setenv("CBFT_ROUTER", "learned")
        assert router_default("priced") == "threshold"

    def test_config_validates_router(self):
        from cometbft_tpu.config import Config

        cfg = Config()
        cfg.crypto.router = "bogus"
        with pytest.raises(ValueError, match="crypto.router"):
            cfg.validate_basic()


class TestMeshRoutePin:
    def test_malformed_pin_warns_once_and_sizes(self, monkeypatch):
        log = _Log()
        sched = _sched(logger=log)
        monkeypatch.setenv("CBFT_MESH_ROUTE", "shardedd")
        for _ in range(5):
            assert sched._pin_route() is None
        assert len(log.errors) == 1, "parse-once cache must warn once"
        # a DIFFERENT malformed value re-parses (and re-warns) once
        monkeypatch.setenv("CBFT_MESH_ROUTE", "both")
        assert sched._pin_route() is None
        assert sched._pin_route() is None
        assert len(log.errors) == 2

    def test_env_flip_takes_effect_next_flush(self, monkeypatch):
        sched = _sched(logger=_Log())
        monkeypatch.setenv("CBFT_MESH_ROUTE", "single")
        assert sched._pin_route() == "single"
        monkeypatch.setenv("CBFT_MESH_ROUTE", "sharded")
        assert sched._pin_route() == "sharded"
        monkeypatch.delenv("CBFT_MESH_ROUTE")
        assert sched._pin_route() is None

    def test_valid_pin_beats_priced_argmin(self, ledger, monkeypatch):
        """Regression for the pin/argmin precedence: the cost model says
        cpu is free, but the operator pinned single — the pin wins and
        the record is tagged "pinned", not "priced"."""
        led = declib.DecisionLedger(
            window=8, ring_interval_s=1e9,
            seed=_seed({"cpu": 0.01, "single": 50.0, "sharded": 50.0}),
        )
        prev = declib.set_default_ledger(led)
        try:
            sched = _sched(logger=_Log())
            # without the pin the argmin takes the free cpu rung
            assert _routed(sched, led, 64) == ("cpu", None, "priced")
            monkeypatch.setenv("CBFT_MESH_ROUTE", "single")
            assert _routed(sched, led, 64) == ("single", "single", "pinned")
        finally:
            declib.set_default_ledger(prev)

    def test_malformed_pin_leaves_priced_router_live(
        self, ledger, monkeypatch
    ):
        monkeypatch.setenv("CBFT_MESH_ROUTE", "not-a-route")
        sched = _sched(logger=_Log())
        assert _routed(sched, ledger, 64) == ("single", None, "priced")


class TestFeasibilityAndRegret:
    def test_infeasible_candidate_cannot_inflate_regret(self):
        led = declib.DecisionLedger(
            window=8, ring_interval_s=1e9,
            seed=_seed({"cpu": 5.0, "single": 10.0, "sharded": 1.0}),
        )
        feas = {
            "cpu": True, "single": True, "sharded": False,
            "indexed": False,
        }
        dec = led.open(8, "test", feasible=feas)
        dec.taken = "single"
        led.finish(dec, 0.010)
        # regret vs the cheapest FEASIBLE candidate (cpu @ 5), not the
        # infeasible sharded rung @ 1
        assert dec.regret_ms == pytest.approx(5.0)
        rec = led.snapshot()["recent"][-1]
        assert rec["feasible"] == feas
        assert rec["regret_ms"] == pytest.approx(5.0)

    def test_legacy_records_count_every_priced_candidate(self):
        led = declib.DecisionLedger(
            window=8, ring_interval_s=1e9,
            seed=_seed({"cpu": 5.0, "single": 10.0, "sharded": 1.0}),
        )
        dec = led.open(8, "test")  # feasible=None: pre-router shape
        dec.taken = "single"
        led.finish(dec, 0.010)
        assert dec.regret_ms == pytest.approx(9.0)

    def test_broken_breakers_leave_only_cpu(self, ledger):
        sup = SimpleNamespace(topology=None)
        sched = _sched(supervisor=sup, logger=_Log())
        feas = sched._decision_feasible(
            [], {"dev0": "broken", "dev1": "broken"}
        )
        assert feas == {
            "cpu": True, "single": False, "sharded": False,
            "indexed": False,
        }
        dec = ledger.open(64, "test", feasible=feas)
        with declib.use(dec):
            label, route, tag = sched._route(64, [])
        assert (label, route, tag) == ("cpu", None, "priced")

    def test_cpu_spec_is_cpu_only(self):
        sched = _sched(spec="cpu", logger=_Log())
        feas = sched._decision_feasible([], None)
        assert feas["cpu"] and not feas["single"]
        assert sched._route(4096, []) == ("cpu", None, "threshold")


class TestRouterEquivalenceAndFallback:
    def test_priced_matches_threshold_when_warm(self, ledger):
        """Warm model, single cheapest: the argmin and the threshold
        ladder must take the SAME route at every flush size (the router
        swap is a perf change, not a behavior change)."""
        priced = _sched(router="priced", logger=_Log())
        thresh = _sched(router="threshold", logger=_Log())
        for n in (1, 4, 16, 64, 256, 1024, 4096):
            lp, rp, tp = _routed(priced, ledger, n)
            lt, rt, tt = _routed(thresh, ledger, n)
            assert (lp, rp) == (lt, rt), f"diverged at n={n}"
            assert tp == "priced" and tt == "threshold"

    def test_cold_model_falls_back_to_threshold(self):
        led = declib.DecisionLedger(window=8, ring_interval_s=1e9)
        prev = declib.set_default_ledger(led)
        try:
            sched = _sched(logger=_Log())
            # no seed, no observations: every candidate unpriced
            assert _routed(sched, led, 64) == ("single", None, "threshold")
        finally:
            declib.set_default_ledger(prev)

    def test_partially_priced_menu_stays_on_thresholds(self):
        # one feasible primary still unpriced -> an argmin over the
        # partial menu would dodge the unpriced route; stay threshold
        led = declib.DecisionLedger(
            window=8, ring_interval_s=1e9,
            seed=_seed({"cpu": 1.0}),
        )
        prev = declib.set_default_ledger(led)
        try:
            sched = _sched(logger=_Log())
            assert _routed(sched, led, 64) == ("single", None, "threshold")
        finally:
            declib.set_default_ledger(prev)

    def test_no_ledger_means_threshold(self):
        prev = declib.set_default_ledger(None)
        try:
            sched = _sched(logger=_Log())
            assert sched._route(64, []) == ("single", None, "threshold")
        finally:
            declib.set_default_ledger(prev)


class _GuardStub:
    """Duck-typed decision ledger for the rollback guard: just the
    watchdog/windowed surface, directly scriptable."""

    def __init__(self):
        self.tripped = None
        self.trips = 0
        self.rate = 0.0
        self.obs = 64
        self.regret_trip = declib.REGRET_TRIP

    def watchdog_state(self):
        return {"tripped": self.tripped, "trips": self.trips}

    def windowed(self):
        return {
            "mape": None, "regret_ms": 0.0,
            "regret_rate": self.rate, "observations": self.obs,
        }


class TestRollbackGuard:
    def test_trip_rolls_back_then_clean_windows_readmit(self):
        log = _Log()
        sched = _sched(logger=log)
        g = _GuardStub()
        assert sched._router_guard(g) is True

        g.tripped = "mape"
        g.trips = 1
        assert sched._router_guard(g) is False
        router = sched.queue_snapshot()["router"]
        assert router["rolled_back"] is True
        assert router["rollbacks"] == 1
        assert router["rollback_cause"] == "mape"
        assert router["live"] == "rolled-back"

        # still tripped: stays rolled back, no double-count
        assert sched._router_guard(g) is False
        assert sched.queue_snapshot()["router"]["rollbacks"] == 1

        # watchdog re-arms: re-admission needs REARM_CLEAN clean checks
        g.tripped = None
        for i in range(ROUTER_REARM_CLEAN - 1):
            assert sched._router_guard(g) is False, f"check {i}"
        assert sched._router_guard(g) is True
        router = sched.queue_snapshot()["router"]
        assert router["rolled_back"] is False
        assert router["readmits"] == 1
        assert router["rollback_cause"] is None

    def test_regret_rate_rolls_back_and_dirty_checks_reset(self):
        sched = _sched(logger=_Log())
        g = _GuardStub()
        g.rate = g.regret_trip * 2
        assert sched._router_guard(g) is False
        assert (
            sched.queue_snapshot()["router"]["rollback_cause"] == "regret"
        )
        # one clean check, then a dirty one: the streak must reset
        g.rate = 0.0
        assert sched._router_guard(g) is False
        g.rate = g.regret_trip  # above the re-admit bar (trip/2)
        assert sched._router_guard(g) is False
        g.rate = 0.0
        for _ in range(ROUTER_REARM_CLEAN - 1):
            assert sched._router_guard(g) is False
        assert sched._router_guard(g) is True

    def test_low_observation_regret_does_not_roll_back(self):
        sched = _sched(logger=_Log())
        g = _GuardStub()
        g.rate = 1.0
        g.obs = declib.MIN_TRIP_OBS - 1
        assert sched._router_guard(g) is True

    def test_rolled_back_route_is_tagged(self, ledger):
        sched = _sched(logger=_Log())
        ledger._tripped = "mape"  # latch the watchdog directly
        assert _routed(sched, ledger, 64) == ("single", None, "rolled-back")
        snap = sched.queue_snapshot()["router"]
        assert snap["rolled_back"] and snap["rollbacks"] == 1


def _valset(n, tag=b"router"):
    keys = [
        ed.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)
    ]
    pks = [k.pub_key().bytes() for k in keys]
    vid = hashlib.sha256(b"".join(pks)).digest()
    return keys, pks, vid


def _flush(keys, tag=b"vote"):
    msgs = [tag + b" %d" % i for i in range(len(keys))]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return msgs, sigs


@pytest.fixture
def store(monkeypatch):
    monkeypatch.setattr(mesh, "n_devices", lambda: 1)
    st = keystore.default_store()
    st.invalidate()
    yield st
    st.invalidate()
    topo = topology.default_topology()
    for i in range(len(topo)):
        topo.set_quarantined(i, False)


def _resident(vid, pks, keys, tag=b"seed"):
    msgs, sigs = _flush(keys, tag)
    assert eb.verify_valset_resident(vid, pks, msgs, sigs) == \
        [True] * len(pks)


_INDEXED_CHEAP = {
    "cpu": 50.0, "single": 5.0, "sharded": 40.0, "indexed": 1.0,
}


class TestIndexedRouting:
    def test_indexed_iff_keys_resident(self, store):
        led = declib.DecisionLedger(
            window=8, ring_interval_s=1e9, seed=_seed(_INDEXED_CHEAP)
        )
        prev = declib.set_default_ledger(led)
        try:
            keys, pks, vid = _valset(4, b"route-idx")
            _resident(vid, pks, keys)
            msgs, sigs = _flush(keys, b"go")
            # items carry PubKey OBJECTS, exactly as scheduler flushes do
            items = [
                (k.pub_key(), m, s) for k, m, s in zip(keys, msgs, sigs)
            ]
            sup = SimpleNamespace(topology=None)
            sched = _sched(supervisor=sup, logger=_Log())

            feas = sched._decision_feasible(items, None)
            assert feas["indexed"] is True
            assert _routed(sched, led, 4, items) == (
                "indexed", "indexed", "priced"
            )

            # residency lost: indexed infeasible, argmin falls to single
            store.invalidate()
            feas = sched._decision_feasible(items, None)
            assert feas["indexed"] is False
            label, route, tag = _routed(sched, led, 4, items)
            assert (label, tag) == ("single", "priced")
            assert route != "indexed"
        finally:
            declib.set_default_ledger(prev)

    def test_unsupervised_never_routes_indexed(self, store):
        led = declib.DecisionLedger(
            window=8, ring_interval_s=1e9, seed=_seed(_INDEXED_CHEAP)
        )
        prev = declib.set_default_ledger(led)
        try:
            keys, pks, vid = _valset(3, b"route-unsup")
            _resident(vid, pks, keys)
            msgs, sigs = _flush(keys)
            items = [
                (k.pub_key(), m, s) for k, m, s in zip(keys, msgs, sigs)
            ]
            sched = _sched(logger=_Log())  # no supervisor
            assert sched._decision_feasible(items, None)["indexed"] is False
            label, route, tag = _routed(sched, led, 3, items)
            assert label == "single"
        finally:
            declib.set_default_ledger(prev)

    def test_indexed_wire_stays_at_100_bytes_per_lane(self, store):
        n = max(64, eb._MIN_PAD)  # pow2 >= the pad floor: no pad waste
        keys, pks, vid = _valset(n, b"route-bpl")
        _resident(vid, pks, keys)
        msgs, sigs = _flush(keys, b"steady")
        wl = wirelib.WireLedger(window=8)
        prev = wirelib.set_default_ledger(wl)
        try:
            assert keystore.verify_batch_indexed(pks, msgs, sigs) == \
                [True] * n
        finally:
            wirelib.set_default_ledger(prev)
        bpl = wl.bytes_per_lane("indexed")
        assert bpl is not None
        assert bpl <= wirelib.ROUTE_BYTES_PER_LANE["indexed"] + 1e-6

    def test_covers_accepts_pubkey_objects(self, store):
        keys, pks, vid = _valset(3, b"route-cov")
        _resident(vid, pks, keys)
        assert keystore.covers(pks)
        assert keystore.covers([k.pub_key() for k in keys])
        stranger = ed.gen_priv_key_from_secret(b"route-cov-x").pub_key()
        assert not keystore.covers([stranger])


class TestEndToEndFlush:
    def test_flush_records_router_tag_and_reconciles(self):
        led = declib.DecisionLedger(window=8, ring_interval_s=1e9)
        prev = declib.set_default_ledger(led)
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=100)
        sched.start()
        try:
            k = ed.gen_priv_key_from_secret(b"router-e2e")
            msg = b"router end to end"
            ok, mask = sched.submit(
                [(k.pub_key(), msg, k.sign(msg))]
            ).result(timeout=30)
            assert ok and mask == [True]
            rec = led.snapshot()["recent"][-1]
            assert rec["taken"] == "cpu"
            assert rec["router"] == "threshold"
            assert rec["feasible"]["cpu"] is True
            snap = sched.queue_snapshot()
            assert snap["routes"]["cpu"] == 1
            assert snap["router"]["last"] == "threshold"
            assert led.snapshot()["counts"].get("cpu") == 1
        finally:
            sched.stop()
            declib.set_default_ledger(prev)


def _audit_sources(recent, router=None, wd=None):
    decisions = {"recent": recent, "watchdog": wd or {}}
    scheduler = {"router": router or {}}
    return decisions, scheduler


class TestRouteAuditAssertLive:
    def test_clean_argmin_passes(self):
        d, s = _audit_sources([{
            "seq": 1, "router": "priced", "taken": "single",
            "predicted_ms": {"cpu": 2.0, "single": 1.0},
            "feasible": {"cpu": True, "single": True},
        }])
        assert route_audit.assert_live(d, s) == []

    def test_divergence_flagged(self):
        d, s = _audit_sources([{
            "seq": 7, "router": "priced", "taken": "single",
            "predicted_ms": {"cpu": 1.0, "single": 10.0},
            "feasible": {"cpu": True, "single": True},
        }])
        problems = route_audit.assert_live(d, s)
        assert len(problems) == 1 and "argmin" in problems[0]

    def test_tolerance_allows_near_ties(self):
        d, s = _audit_sources([{
            "seq": 2, "router": "priced", "taken": "single",
            "predicted_ms": {"cpu": 1.0, "single": 1.05},
            "feasible": {"cpu": True, "single": True},
        }])
        assert route_audit.assert_live(d, s, tolerance=0.10) == []
        assert route_audit.assert_live(d, s, tolerance=0.01)

    def test_infeasible_taken_flagged(self):
        d, s = _audit_sources([{
            "seq": 3, "router": "priced", "taken": "sharded",
            "predicted_ms": {"single": 1.0, "sharded": 0.5},
            "feasible": {"single": True, "sharded": False},
        }])
        problems = route_audit.assert_live(d, s)
        assert len(problems) == 1 and "infeasible" in problems[0]

    def test_unpriced_taken_flagged(self):
        d, s = _audit_sources([{
            "seq": 4, "router": "priced", "taken": "single",
            "predicted_ms": {"cpu": 1.0, "single": None},
            "feasible": {"cpu": True, "single": True},
        }])
        problems = route_audit.assert_live(d, s)
        assert len(problems) == 1 and "unpriced" in problems[0]

    def test_non_priced_records_are_not_judged(self):
        d, s = _audit_sources([{
            "seq": 5, "router": "threshold", "taken": "single",
            "predicted_ms": {"cpu": 1.0, "single": 10.0},
            "feasible": {"cpu": True, "single": True},
        }])
        assert route_audit.assert_live(d, s) == []

    def test_rollback_without_cause_flagged(self):
        d, s = _audit_sources(
            [], router={"rolled_back": True, "rollback_cause": None}
        )
        problems = route_audit.assert_live(d, s)
        assert len(problems) == 1 and "without" in problems[0]

    def test_rollback_without_trip_flagged(self):
        d, s = _audit_sources(
            [],
            router={"rolled_back": True, "rollback_cause": "mape"},
            wd={"tripped": None, "trips": 0},
        )
        assert len(route_audit.assert_live(d, s)) == 1

    def test_cli_gate_exit_codes(self, tmp_path):
        rec = {
            "seq": 1, "router": "priced", "taken": "single",
            "predicted_ms": {"cpu": 2.0, "single": 1.0},
            "feasible": {"cpu": True, "single": True},
        }
        snap = {
            "slo": {},
            "sources": {
                "decisions": {
                    "counts": {"single": 1}, "windowed": {},
                    "profiles": [], "recent": [rec],
                    "watchdog": {"tripped": None, "trips": 0},
                },
                "scheduler": {
                    "routes": {"single": 1},
                    "router": {
                        "mode": "priced", "live": "priced",
                        "rolled_back": False, "rollbacks": 0,
                        "readmits": 0, "rollback_cause": None,
                    },
                },
            },
        }
        path = tmp_path / "snap.json"
        path.write_text(json.dumps(snap))
        assert route_audit.main([str(path), "--assert-live"]) == 0
        rec["predicted_ms"] = {"cpu": 1.0, "single": 10.0}
        path.write_text(json.dumps(snap))
        assert route_audit.main([str(path), "--assert-live"]) == 2
        # without the flag the divergence is not judged
        assert route_audit.main([str(path)]) == 0

    def test_justified_rollback_passes(self):
        d, s = _audit_sources(
            [],
            router={"rolled_back": True, "rollback_cause": "mape"},
            wd={"tripped": "mape", "trips": 1},
        )
        assert route_audit.assert_live(d, s) == []
        d, s = _audit_sources(
            [],
            router={"rolled_back": True, "rollback_cause": "regret"},
            wd={"tripped": None, "trips": 0},
        )
        assert route_audit.assert_live(d, s) == []
