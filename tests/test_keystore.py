"""Generational device key store — staleness must be undispatchable.

Valset rotation, topology generation bumps, and quarantine re-slices
each invalidate the device pubkey table: a stale-generation dispatch
MISSES (indexed path returns None, resident path rebuilds) and never
verifies against old keys or an old device slicing. Runs on the virtual
CPU mesh (conftest.py); the indexed table is single-device only, so
these tests pin n_devices to 1.
"""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.tpu import ed25519_batch as eb
from cometbft_tpu.crypto.tpu import keystore, mesh, topology


def _valset(n, tag=b"ks"):
    keys = [ed.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    pks = [k.pub_key().bytes() for k in keys]
    vid = hashlib.sha256(b"".join(pks)).digest()
    return keys, pks, vid


def _flush(keys, tag=b"vote"):
    msgs = [tag + b" %d" % i for i in range(len(keys))]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return msgs, sigs


def _cpu(pks, msgs, sigs):
    return [
        ed.PubKeyEd25519(p).verify_signature(m, s)
        for p, m, s in zip(pks, msgs, sigs)
    ]


@pytest.fixture
def store(monkeypatch):
    """Single-device view + a store drained before AND after, with the
    topology quarantine state restored so generation bumps made here
    don't leak into other tests' plans."""
    monkeypatch.setattr(mesh, "n_devices", lambda: 1)
    st = keystore.default_store()
    st.invalidate()
    yield st
    st.invalidate()
    topo = topology.default_topology()
    for i in range(len(topo)):
        topo.set_quarantined(i, False)


def _resident(vid, pks, keys, tag=b"seed"):
    """Build (or refresh) the resident entry by running one real commit
    verification through the store."""
    msgs, sigs = _flush(keys, tag)
    got = eb.verify_valset_resident(vid, pks, msgs, sigs)
    assert got == [True] * len(pks)


class TestValsetRotation:
    def test_rotation_is_a_miss_not_a_reuse(self, store):
        keys_a, pks_a, vid_a = _valset(4, b"rot-a")
        _resident(vid_a, pks_a, keys_a)
        base = store.snapshot()["stats"]

        # same flush again: pure hit, no upload
        _resident(vid_a, pks_a, keys_a, b"again")
        s = store.snapshot()["stats"]
        assert s["hits"] == base["hits"] + 1
        assert s["uploads"] == base["uploads"]

        # rotated valset: different digest -> miss + fresh upload,
        # old entry untouched alongside
        keys_b, pks_b, vid_b = _valset(4, b"rot-b")
        _resident(vid_b, pks_b, keys_b)
        snap = store.snapshot()
        assert snap["stats"]["uploads"] == base["uploads"] + 1
        assert len(snap["entries"]) == 2
        gens = [e["generation"] for e in snap["entries"]]
        assert len(set(gens)) == 2, "each upload gets its own generation"

    def test_lru_eviction_at_cache_max(self, store):
        vids = []
        for i in range(keystore.CACHE_MAX + 1):
            keys, pks, vid = _valset(3, b"lru-%d" % i)
            _resident(vid, pks, keys)
            vids.append(vid)
        with store._mtx:
            held = list(store._entries.keys())
        assert len(held) == keystore.CACHE_MAX
        assert vids[0] not in held, "oldest valset evicted"
        assert vids[-1] in held


class TestTopologyGenerationStaleness:
    def test_quarantine_bump_makes_indexed_dispatch_miss(self, store):
        keys, pks, vid = _valset(4, b"topo")
        _resident(vid, pks, keys)
        msgs, sigs = _flush(keys, b"indexed")

        got = keystore.verify_batch_indexed(pks, msgs, sigs)
        assert got == [True] * 4, "fresh entry must serve the flush"

        topo = topology.default_topology()
        assert topo.set_quarantined(0, True), "membership must change"
        before = store.snapshot()["stats"]["stale_drops"]
        assert keystore.verify_batch_indexed(pks, msgs, sigs) is None, (
            "stale-generation dispatch must MISS, not verify against "
            "the old table"
        )
        assert store.snapshot()["stats"]["stale_drops"] == before + 1
        assert store.snapshot()["entries"] == [], "stale entry dropped"

        # un-quarantine: ANOTHER generation bump — rebuilding under the
        # old generation would be just as wrong
        assert topo.set_quarantined(0, False)
        assert keystore.verify_batch_indexed(pks, msgs, sigs) is None

        # resident path rebuilds under the current generation and the
        # indexed path serves again
        _resident(vid, pks, keys, b"rebuilt")
        entry = store.snapshot()["entries"][0]
        assert entry["topo_generation"] == topo.generation()
        assert keystore.verify_batch_indexed(pks, msgs, sigs) == [True] * 4

    def test_stale_entry_never_verifies_old_keys(self, store):
        # Adversarial rotation: entry built from keys A; topology bumps;
        # the SAME valset_id is re-registered with keys B (as a re-slice
        # rebuild would). get() must rebuild from B — returning the
        # cached A-entry would verify A-signed flushes forever.
        keys_a, pks_a, vid = _valset(3, b"stale-a")
        _resident(vid, pks_a, keys_a)

        topology.default_topology().set_quarantined(1, True)

        keys_b, _, _ = _valset(3, b"stale-b")
        pks_b = [k.pub_key().bytes() for k in keys_b]
        msgs, sigs_a = _flush(keys_a, b"old-sig")
        # flush signed by the OLD keys, presented with the NEW valset
        got = eb.verify_valset_resident(vid, pks_b, msgs, sigs_a)
        assert got == [False] * 3, (
            "stale table reuse would have accepted these"
        )
        entry = store.snapshot()["entries"][0]
        assert entry["topo_generation"] == (
            topology.default_topology().generation()
        )
        # and the new keys' own signatures verify against the rebuilt rows
        msgs_b, sigs_b = _flush(keys_b, b"new-sig")
        assert eb.verify_valset_resident(vid, pks_b, msgs_b, sigs_b) == (
            [True] * 3
        )

    def test_explicit_invalidate(self, store):
        keys, pks, vid = _valset(3, b"inv")
        _resident(vid, pks, keys)
        gen0 = store.snapshot()["generation"]
        assert store.invalidate(vid) == 1
        snap = store.snapshot()
        assert snap["entries"] == []
        assert snap["generation"] == gen0 + 1
        assert store.invalidate(vid) == 0, "double-drop is a no-op"


class TestIndexedDispatch:
    def test_verdicts_match_cpu_and_count_lanes(self, store):
        keys, pks, vid = _valset(5, b"idx")
        _resident(vid, pks, keys)
        msgs, sigs = _flush(keys, b"mix")
        bad = bytearray(sigs[2])
        bad[10] ^= 1
        sigs[2] = bytes(bad)

        before = store.snapshot()["stats"]
        got = keystore.verify_batch_indexed(pks, msgs, sigs)
        assert got == _cpu(pks, msgs, sigs)
        assert got == [True, True, False, True, True]
        s = store.snapshot()["stats"]
        assert s["indexed_dispatches"] == before["indexed_dispatches"] + 1
        assert s["indexed_lanes"] == before["indexed_lanes"] + 5

    def test_repeated_lanes_gather_same_row(self, store):
        # one validator signing several lanes — the index vector repeats
        keys, pks, vid = _valset(3, b"rep")
        _resident(vid, pks, keys)
        k = keys[1]
        msgs = [b"dup %d" % i for i in range(4)]
        sigs = [k.sign(m) for m in msgs]
        got = keystore.verify_batch_indexed(
            [pks[1]] * 4, msgs, sigs
        )
        assert got == [True] * 4

    def test_unknown_key_falls_back(self, store):
        keys, pks, vid = _valset(3, b"fb")
        _resident(vid, pks, keys)
        stranger = ed.gen_priv_key_from_secret(b"fb-stranger")
        msgs, sigs = _flush(keys + [stranger], b"fall")
        assert keystore.verify_batch_indexed(
            pks + [stranger.pub_key().bytes()], msgs, sigs
        ) is None, "flush not fully covered by one entry -> fallback"

    def test_sharded_mesh_falls_back(self, store, monkeypatch):
        keys, pks, vid = _valset(3, b"sh")
        _resident(vid, pks, keys)
        msgs, sigs = _flush(keys)
        monkeypatch.setattr(mesh, "n_devices", lambda: 2)
        assert keystore.verify_batch_indexed(pks, msgs, sigs) is None

    def test_empty_flush(self, store):
        assert keystore.verify_batch_indexed([], [], []) == []


class TestSnapshotPlumbing:
    def test_scheduler_snapshot_carries_keystore(self, store):
        from cometbft_tpu.crypto.batch import BackendSpec
        from cometbft_tpu.crypto.scheduler import VerifyScheduler

        keys, pks, vid = _valset(3, b"snap")
        _resident(vid, pks, keys)
        s = VerifyScheduler(spec=BackendSpec("cpu"))
        snap = s.queue_snapshot()  # not started: snapshot still works
        assert "keystore" in snap
        assert snap["keystore"]["entries"][0]["keys"] == 3
        assert set(snap["keystore"]["stats"]) >= {
            "hits", "misses", "uploads", "stale_drops",
            "indexed_dispatches",
        }

    def test_residency_summary_for_decision_plane(self, store):
        # PR 15: the cheap per-flush summary the decision ledger embeds
        # in every RouteDecision (and the telemetry keystore source)
        empty = store.residency()
        assert empty["entries"] == 0 and empty["keys"] == 0
        # stats survive invalidate(): hit_rate is None only on a virgin
        # store, else a ratio
        assert empty["hit_rate"] is None or 0.0 <= empty["hit_rate"] <= 1.0
        keys, pks, vid = _valset(4, b"resid")
        _resident(vid, pks, keys)
        msgs, sigs = _flush(keys, b"resid-hit")
        assert eb.verify_valset_resident(vid, pks, msgs, sigs) == \
            [True] * 4
        res = store.residency()
        assert res["entries"] == 1 and res["keys"] == 4
        assert res["generation"] >= 1
        assert 0.0 < res["hit_rate"] <= 1.0
        assert isinstance(res["indexed_dispatches"], int)


class TestChurnThrash:
    """PR 18: valset churn faster than flushes drain the cache must
    never yank an in-flight table (pins) and must be visible as the
    ``keystore_thrash`` counter (evictions of never-hit entries).
    PR 30: host-only registrations are bounded by key rows, not by the
    device entries' count; here the bound is CACHE_MAX three-key sets."""

    @pytest.fixture(autouse=True)
    def _room_for_cache_max_sets(self, store, monkeypatch):
        monkeypatch.setattr(store, "_max_host_keys", 3 * keystore.CACHE_MAX)

    def _pks(self, tag, n=3):
        return [hashlib.sha256(tag + b"-%d" % i).digest()
                for i in range(n)]

    def test_pinned_entry_survives_lru_pressure(self, store):
        vid_a = hashlib.sha256(b"pin-a").digest()
        store.register(vid_a, self._pks(b"pin-a"))
        assert store.pin(vid_a)
        try:
            # churn well past CACHE_MAX while the dispatch is in flight
            for i in range(keystore.CACHE_MAX + 2):
                vid = hashlib.sha256(b"pin-press-%d" % i).digest()
                store.register(vid, self._pks(b"pin-press-%d" % i))
            with store._mtx:
                held = set(store._entries.keys())
            assert vid_a in held, "pinned entry yanked under pressure"
            assert len(held) == keystore.CACHE_MAX
        finally:
            store.unpin(vid_a)
        # eviction resumes once the dispatch lands: the next insert
        # takes out the (now oldest, unpinned) formerly-pinned entry
        vid_z = hashlib.sha256(b"pin-z").digest()
        store.register(vid_z, self._pks(b"pin-z"))
        with store._mtx:
            held = set(store._entries.keys())
        assert vid_a not in held
        assert vid_z in held

    def test_pin_context_manager_balances(self, store):
        vid = hashlib.sha256(b"pin-ctx").digest()
        store.register(vid, self._pks(b"pin-ctx"))
        with store.pinned(vid) as ok:
            assert ok
            with store._mtx:
                assert store._entries[vid].pins == 1
        with store._mtx:
            assert store._entries[vid].pins == 0
        # pinning a missing entry reports False and never raises
        with store.pinned(b"\x00" * 32) as ok:
            assert not ok

    def test_thrash_counts_never_hit_evictions(self, store):
        base = store.residency()["thrash"]
        # the adversary's churn shape: rotate valsets faster than any
        # flush touches them — every eviction is of a never-hit entry
        for i in range(keystore.CACHE_MAX + 3):
            vid = hashlib.sha256(b"thrash-%d" % i).digest()
            store.register(vid, self._pks(b"thrash-%d" % i))
        assert store.residency()["thrash"] == base + 3

    def test_served_entries_do_not_count_as_thrash(self, store):
        base = store.residency()["thrash"]
        # entries that served at least one flush are working-set
        # turnover, not thrash
        vids = []
        for i in range(keystore.CACHE_MAX):
            vid = hashlib.sha256(b"used-%d" % i).digest()
            store.register(vid, self._pks(b"used-%d" % i))
            store.register(vid, self._pks(b"used-%d" % i))  # a hit
            vids.append(vid)
        for i in range(keystore.CACHE_MAX):
            vid = hashlib.sha256(b"churn-%d" % i).digest()
            store.register(vid, self._pks(b"churn-%d" % i))
        with store._mtx:
            held = set(store._entries.keys())
        assert all(v not in held for v in vids), "all churned out"
        assert store.residency()["thrash"] == base


class TestRegistrationFreshness:
    """PR 30: the handshake's generation counts entries that LEAVE the
    store; an insert beside a client's registration stales nobody, and
    the service's 4.8 KB host-only registrations do not compete with the
    device entries for CACHE_MAX slots."""

    def _pks(self, tag, n=150):
        return [hashlib.sha256(tag + b"-%d" % i).digest()
                for i in range(n)]

    def _register(self, store, tag, n=150):
        pks = self._pks(tag, n)
        vid = hashlib.sha256(b"".join(pks)).digest()[:16]
        store.register(vid, pks)
        return vid

    def test_eight_chains_fit_and_an_insert_stales_nobody(self, store):
        gen = store.generation()
        base = store.snapshot()["stats"]
        vids = [self._register(store, b"chain-%d" % c) for c in range(8)]
        assert store.generation() == gen
        stats = store.snapshot()["stats"]
        assert stats["evictions"] == base["evictions"]
        assert stats["keystore_thrash"] == base["keystore_thrash"]
        for vid in vids:  # the first registered is as fresh as the last
            assert store.entry_for(vid, gen) is not None
        uploads = [e["generation"] for e in store.snapshot()["entries"]]
        assert len(set(uploads)) == 8, "each upload keeps its own number"

    def test_host_registrations_leave_the_device_entries_alone(self, store):
        keys, pks, vid = _valset(3, b"dev-entry")
        _resident(vid, pks, keys)
        for c in range(keystore.CACHE_MAX + 4):
            self._register(store, b"many-%d" % c)
        with store._mtx:
            assert vid in store._entries
            assert len(store._entries) == keystore.CACHE_MAX + 5
        # and a host registration is never the victim of device pressure
        first = self._register(store, b"many-0")
        for i in range(keystore.CACHE_MAX + 1):
            k, p, v = _valset(3, b"dev-press-%d" % i)
            _resident(v, p, k)
        with store._mtx:
            assert first in store._entries
            assert vid not in store._entries, "oldest device entry evicted"

    def test_an_eviction_stales_every_client(self, store, monkeypatch):
        monkeypatch.setattr(store, "_max_host_keys", 2 * 150)
        a = self._register(store, b"ev-a")
        b = self._register(store, b"ev-b")
        gen = store.generation()
        evictions = store.snapshot()["stats"]["evictions"]
        self._register(store, b"ev-c")  # evicts a
        assert store.generation() == gen + 1
        assert store.snapshot()["stats"]["evictions"] == evictions + 1
        assert store.entry_for(b, gen) is None, "b's client must resync"
        assert store.entry_for(b, gen + 1) is not None
        assert store.entry_for(a, gen + 1) is None

    def test_invalidate_refuses_the_old_generation(self, store):
        vid = self._register(store, b"inv")
        gen = store.generation()
        assert store.entry_for(vid, gen) is not None
        assert store.invalidate(vid) == 1
        before = store.snapshot()["stats"]["stale_drops"]
        assert store.entry_for(vid, gen) is None
        assert store.snapshot()["stats"]["stale_drops"] == before + 1
        assert store.generation() == gen + 1

    def test_topology_bump_refuses_and_register_rebuilds(self, store):
        vid = self._register(store, b"topo-reg")
        gen = store.generation()
        topo = topology.default_topology()
        assert topo.set_quarantined(0, True)
        before = store.snapshot()["stats"]["stale_drops"]
        assert store.entry_for(vid, gen) is None
        assert store.snapshot()["stats"]["stale_drops"] == before + 1
        assert store.generation() == gen + 1
        with store._mtx:
            assert vid not in store._entries
        again = self._register(store, b"topo-reg")
        assert again == vid
        entry = store.entry_for(vid, store.generation())
        assert entry is not None
        assert entry.topo_generation == topo.generation()
