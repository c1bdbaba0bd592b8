"""Measurement-driven CPU↔device routing (crypto/tpu/calibrate.py and
its consumers).

Round 5's by-construction thresholds routed the Merkle mega-set onto a
device path that LOST 4.5× on the round-5 shared chip; routing is now gated
on a crossover table measured at node warmup. These tests pin the
contract on CPU-only CI: no table → no device claim (Merkle stays on
host, ed25519 keeps the conservative floor), a recorded table opens
routing exactly at the measured crossover, env knobs keep operator
precedence, and the resident commit path is reached through
ValidatorSet.verify_commit — including from concurrent threads racing
the resident-cache build.
"""

import hashlib
import json
import os
import threading

import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import merkle as cpu_merkle
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.tpu import calibrate, ed25519_batch, keystore
from cometbft_tpu.crypto.tpu import merkle as tpu_merkle
from cometbft_tpu.types import test_util
from cometbft_tpu.types.validator_set import Fraction

CHAIN_ID = "routing-chain"


@pytest.fixture
def clean_routing(monkeypatch):
    """No env overrides, no table: the fresh-node / CI posture."""
    monkeypatch.delenv("CBFT_TPU_MIN_BATCH", raising=False)
    monkeypatch.delenv("CBFT_TPU_MERKLE_MIN_LEAVES", raising=False)
    monkeypatch.delenv("CBFT_TPU_CALIBRATION", raising=False)
    calibrate.set_table_path(None)
    yield
    calibrate.set_table_path(None)


def _write_table(path, **floors):
    calibrate.save_table({"version": calibrate.TABLE_VERSION, **floors}, path)
    calibrate.set_table_path(path)


class TestCrossover:
    """_crossover: smallest measured size from which the device wins at
    every larger measured size too."""

    def test_monotonic_win_opens_at_smallest_winning_size(self):
        pts = {256: (5.0, 10.0), 512: (4.0, 10.0), 1024: (3.0, 10.0)}
        assert calibrate._crossover(pts) == 256

    def test_device_never_wins(self):
        pts = {256: (20.0, 10.0), 1024: (15.0, 10.0)}
        assert calibrate._crossover(pts) is None

    def test_lucky_window_does_not_open_lower_sizes(self):
        # device wins at 256 and 1024 but loses at 512: the mid-sweep
        # loss must cap the crossover at 1024, not 256
        pts = {256: (5.0, 10.0), 512: (20.0, 10.0), 1024: (3.0, 10.0)}
        assert calibrate._crossover(pts) == 1024

    def test_win_only_at_largest(self):
        pts = {256: (20.0, 10.0), 512: (20.0, 10.0), 1024: (3.0, 10.0)}
        assert calibrate._crossover(pts) == 1024


class TestTableIO:
    def test_roundtrip_and_floor_accessors(self, tmp_path, clean_routing):
        path = str(tmp_path / "cal.json")
        _write_table(path, merkle_min_leaves=512, ed25519_min_batch=256)
        assert calibrate.merkle_min_leaves() == 512
        assert calibrate.ed25519_min_batch() == 256

    def test_wrong_version_ignored(self, tmp_path, clean_routing):
        path = str(tmp_path / "cal.json")
        with open(path, "w") as f:
            json.dump(
                {"version": calibrate.TABLE_VERSION + 1, "merkle_min_leaves": 1},
                f,
            )
        calibrate.set_table_path(path)
        assert calibrate.load_table() is None
        assert calibrate.merkle_min_leaves() is None

    def test_garbage_file_ignored(self, tmp_path, clean_routing):
        path = str(tmp_path / "cal.json")
        with open(path, "w") as f:
            f.write("{torn write")
        calibrate.set_table_path(path)
        assert calibrate.load_table() is None

    def test_null_and_bogus_floors_mean_unproven(self, tmp_path, clean_routing):
        # device never won → crossover None; booleans/negatives likewise
        path = str(tmp_path / "cal.json")
        _write_table(path, merkle_min_leaves=None, ed25519_min_batch=-5)
        assert calibrate.merkle_min_leaves() is None
        assert calibrate.ed25519_min_batch() is None

    def test_missing_path_or_file(self, clean_routing):
        assert calibrate.table_path() is None
        assert calibrate.load_table() is None
        calibrate.set_table_path("/nonexistent/nowhere/cal.json")
        assert calibrate.load_table() is None

    def test_rerecorded_table_picked_up_without_restart(
        self, tmp_path, clean_routing
    ):
        path = str(tmp_path / "cal.json")
        _write_table(path, ed25519_min_batch=512)
        assert calibrate.ed25519_min_batch() == 512
        _write_table(path, ed25519_min_batch=128)
        # the (path, mtime) cache must notice the new file; force a
        # distinct mtime in case the fs clock granularity hid the rewrite
        st = os.stat(path)
        os.utime(path, (st.st_atime, st.st_mtime + 2))
        assert calibrate.ed25519_min_batch() == 128


class TestRouteCostSeed:
    """route_cost_seed_ms: the priced router's cold seed, from the
    calibration table's measured points."""

    _POINTS = {
        "256": {"device_ms": 4.0, "cpu_ms": 8.0},
        "1024": {"device_ms": 6.0, "cpu_ms": 30.0},
    }

    def test_cpu_and_single_scale_the_nearest_point(
        self, tmp_path, clean_routing
    ):
        _write_table(str(tmp_path / "cal.json"), ed25519=self._POINTS)
        assert calibrate.route_cost_seed_ms("single", 2048) == 12.0
        assert calibrate.route_cost_seed_ms("cpu", 128) == 4.0
        assert calibrate.route_cost_seed_ms("indexed", 256) is None

    def test_an_old_hash_section_prices_nothing(
        self, tmp_path, clean_routing
    ):
        # a table recorded while the calibration still swept the hash
        # placement keeps loading; its hash section is simply not read
        _write_table(
            str(tmp_path / "cal.json"), ed25519=self._POINTS,
            hash={"64": {"device_ms": 1.0, "host_ms": 2.0}},
            hash_device_min_batch=64,
        )
        assert calibrate.load_table() is not None
        assert calibrate.route_cost_seed_ms("device_hash", 64) is None
        assert calibrate.route_cost_seed_ms("single", 256) == 4.0


class TestEd25519FloorPrecedence:
    """ed25519_routing_floor: env > configured min_batch > table > 1024."""

    def test_default_without_any_signal(self, clean_routing):
        assert cbatch.ed25519_routing_floor() == 1024

    def test_table_beats_default(self, tmp_path, clean_routing):
        _write_table(str(tmp_path / "cal.json"), ed25519_min_batch=256)
        assert cbatch.ed25519_routing_floor() == 256

    def test_config_beats_table(self, tmp_path, clean_routing):
        _write_table(str(tmp_path / "cal.json"), ed25519_min_batch=256)
        assert cbatch.ed25519_routing_floor(64) == 64

    def test_env_beats_everything(self, tmp_path, clean_routing, monkeypatch):
        _write_table(str(tmp_path / "cal.json"), ed25519_min_batch=256)
        monkeypatch.setenv("CBFT_TPU_MIN_BATCH", "7")
        assert cbatch.ed25519_routing_floor(64) == 7


class TestMerkleDeviceWins:
    def test_no_table_means_host(self, clean_routing):
        # the acceptance regression: 10k leaves must NOT route to the
        # device without a measured crossover proving the win
        assert not tpu_merkle.device_wins(10_000)
        assert not tpu_merkle.device_wins(10**9)

    def test_table_opens_routing_at_the_measured_floor(
        self, tmp_path, clean_routing
    ):
        _write_table(str(tmp_path / "cal.json"), merkle_min_leaves=512)
        assert tpu_merkle.device_wins(512)
        assert tpu_merkle.device_wins(10_000)
        assert not tpu_merkle.device_wins(511)

    def test_device_never_won_stays_host(self, tmp_path, clean_routing):
        _write_table(str(tmp_path / "cal.json"), merkle_min_leaves=None)
        assert not tpu_merkle.device_wins(10_000)

    def test_env_keeps_operator_precedence(self, clean_routing, monkeypatch):
        monkeypatch.setenv("CBFT_TPU_MERKLE_MIN_LEAVES", "128")
        assert tpu_merkle.device_wins(128)
        assert not tpu_merkle.device_wins(127)

    def test_host_tree_used_without_verdict(self, clean_routing, monkeypatch):
        # end-to-end: with parallel enabled but no table, the device
        # kernel must never be invoked
        def boom(*a, **k):
            raise AssertionError("device merkle dispatched without verdict")

        monkeypatch.setattr(tpu_merkle, "hash_from_byte_slices", boom)
        monkeypatch.setattr(cpu_merkle, "_parallel_enabled", True)
        items = [b"leaf %d" % i for i in range(300)]
        root = cpu_merkle.hash_from_byte_slices(items)
        assert len(root) == 32


class TestResidentCommitRouting:
    """verify_commit under the tpu backend reaches the resident path
    through the configured floor (BackendSpec), not an env re-read."""

    def _fixture(self, n=4):
        vals, privs = test_util.deterministic_validator_set(n, 10)
        bid = test_util.make_block_id()
        commit = test_util.make_commit(bid, 5, 0, vals, privs, CHAIN_ID)
        return vals, bid, commit

    def _spy(self, monkeypatch):
        calls = []
        real = ed25519_batch.verify_valset_resident

        def spy(vid, pks, msgs, sigs):
            calls.append(len(pks))
            return real(vid, pks, msgs, sigs)

        monkeypatch.setattr(ed25519_batch, "verify_valset_resident", spy)
        return calls

    def test_all_three_verify_commit_variants_route_resident(
        self, clean_routing, monkeypatch
    ):
        vals, bid, commit = self._fixture()
        calls = self._spy(monkeypatch)
        spec = BackendSpec("tpu", min_batch=1)
        vals.verify_commit(CHAIN_ID, bid, 5, commit, backend=spec)
        vals.verify_commit_light(CHAIN_ID, bid, 5, commit, backend=spec)
        vals.verify_commit_light_trusting(
            CHAIN_ID, commit, trust_level=Fraction(1, 3), backend=spec
        )
        assert len(calls) == 3

    def test_cpu_backend_never_touches_resident(
        self, clean_routing, monkeypatch
    ):
        vals, bid, commit = self._fixture()
        calls = self._spy(monkeypatch)
        vals.verify_commit(CHAIN_ID, bid, 5, commit, backend="cpu")
        assert calls == []

    def test_floor_gates_the_route(self, clean_routing, monkeypatch):
        vals, bid, commit = self._fixture()
        calls = self._spy(monkeypatch)
        spec = BackendSpec("tpu", min_batch=1000)  # 4 lanes < floor
        vals.verify_commit(CHAIN_ID, bid, 5, commit, backend=spec)
        assert calls == []

    def test_resident_verdict_matches_cpu_backend(self, clean_routing):
        vals, bid, commit = self._fixture(n=6)
        spec = BackendSpec("tpu", min_batch=1)
        # valid commit accepted by both
        vals.verify_commit(CHAIN_ID, bid, 5, commit, backend=spec)
        vals.verify_commit(CHAIN_ID, bid, 5, commit, backend="cpu")
        # corrupt one signature: both must reject
        bad = commit.signatures[2]
        bad_sig = bytes([bad.signature[0] ^ 1]) + bad.signature[1:]
        commit.signatures[2] = type(bad)(
            bad.block_id_flag, bad.validator_address, bad.timestamp, bad_sig
        )
        for backend in (spec, "cpu"):
            with pytest.raises(Exception):
                vals.verify_commit(CHAIN_ID, bid, 5, commit, backend=backend)


class TestConcurrentResident:
    def test_two_threads_race_the_cache_build(self, clean_routing):
        """Two threads verifying the same (uncached) valset must both
        return the correct mask and leave exactly ONE resident entry —
        the _get_resident adopt-the-race-winner contract."""
        from cometbft_tpu.crypto import ed25519 as ed

        keys = [
            ed.gen_priv_key_from_secret(b"race-%d" % i) for i in range(8)
        ]
        pks = [k.pub_key().bytes() for k in keys]
        msgs = [b"race vote %d" % i for i in range(8)]
        sigs = [k.sign(m) for k, m in zip(keys, msgs)]
        vid = hashlib.sha256(b"".join(pks)).digest()
        keystore.default_store().invalidate(vid)

        barrier = threading.Barrier(2)
        results, errors = [None, None], []

        def run(slot):
            try:
                barrier.wait(timeout=30)
                results[slot] = ed25519_batch.verify_valset_resident(
                    vid, pks, msgs, sigs
                )
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        threads = [
            threading.Thread(target=run, args=(i,), daemon=True)
            for i in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        assert results[0] == results[1] == [True] * 8
        assert keystore.default_store().entry_for(vid) is not None

    def test_two_threads_verify_commit_concurrently(self, clean_routing):
        vals, privs = test_util.deterministic_validator_set(4, 10)
        bid = test_util.make_block_id()
        commit = test_util.make_commit(bid, 5, 0, vals, privs, CHAIN_ID)
        spec = BackendSpec("tpu", min_batch=1)
        barrier = threading.Barrier(2)
        errors = []

        def run():
            try:
                barrier.wait(timeout=30)
                vals.verify_commit(CHAIN_ID, bid, 5, commit, backend=spec)
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        threads = [threading.Thread(target=run, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
