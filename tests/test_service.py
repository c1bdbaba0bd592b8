"""Verify-as-a-service (PR 17): frame codec, cross-client demux,
disconnect containment, malformed-frame refusal, and the keystore
generation handshake.

The RPC payload IS the PR 13 wire format — compact 128 B/lane rows (or
96 B rsh + 4 B index when a registered valset covers the request), so
bytes-per-lane over the socket is exactly the device wire's. These
tests pin the frame codec against truncation/garbage at every offset,
prove one merged flush fans verdicts back out to the right client, and
walk the stale-generation resync ladder end to end over a real Unix
socket. Runs on the virtual CPU mesh (conftest.py)."""

import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import service as svc
from cometbft_tpu.crypto.scheduler import VerifyScheduler

_LEN = struct.Struct("<I")


def _batch(n, tag=b"svc", bad=()):
    """(pk, msg, sig) triples; lanes in ``bad`` get a corrupted sig."""
    keys = [ed.gen_priv_key_from_secret(tag + b"-%d" % i) for i in range(n)]
    items = []
    for i, k in enumerate(keys):
        msg = tag + b" msg %d" % i
        sig = k.sign(msg)
        if i in bad:
            sig = bytes(sig[:-1]) + bytes([sig[-1] ^ 0x01])
        items.append((k.pub_key(), msg, sig))
    return items


def _expected(items):
    return [
        ed.PubKeyEd25519(svc._pk_bytes(pk)).verify_signature(m, s)
        for pk, m, s in items
    ]


# ---------------------------------------------------------------------------
# frame codec: round-trip properties + typed refusal of garbage
# ---------------------------------------------------------------------------


class TestFrameCodec:
    def test_header_is_40_bytes(self):
        assert svc.HEADER_BYTES == 40

    @pytest.mark.parametrize("ftype", [
        svc.FT_HELLO, svc.FT_CLIENT_HELLO, svc.FT_REQ, svc.FT_RESP,
        svc.FT_ERR, svc.FT_REGISTER, svc.FT_REGISTERED,
    ])
    @pytest.mark.parametrize("nbytes", [0, 1, 100, 128, 4097])
    def test_round_trip_every_field(self, ftype, nbytes):
        payload = bytes((i * 7 + ftype) % 256 for i in range(nbytes))
        vid = bytes(range(16))
        buf = svc.encode_frame(
            ftype, qclass=3, kind=svc.KIND_INDEXED, req_id=2**63 + 9,
            n_lanes=2**31 + 1, generation=0xDEADBEEF, valset_id=vid,
            payload=payload,
        )
        (length,) = _LEN.unpack(buf[:4])
        assert length == len(buf) - 4 == svc.HEADER_BYTES + nbytes
        f = svc.decode_frame(buf[4:])
        assert f.ftype == ftype
        assert f.qclass == 3
        assert f.kind == svc.KIND_INDEXED
        assert f.req_id == 2**63 + 9
        assert f.n_lanes == 2**31 + 1
        assert f.generation == 0xDEADBEEF
        assert f.valset_id == vid
        assert f.payload == payload

    def test_valset_id_pads_and_truncates_to_16(self):
        f = svc.decode_frame(svc.encode_frame(
            svc.FT_REQ, valset_id=b"ab",
        )[4:])
        assert f.valset_id == b"ab" + b"\x00" * 14
        f = svc.decode_frame(svc.encode_frame(
            svc.FT_REQ, valset_id=b"x" * 40,
        )[4:])
        assert f.valset_id == b"x" * 16

    def test_bad_magic_is_typed_malformed(self):
        buf = bytearray(svc.encode_frame(svc.FT_REQ)[4:])
        buf[:4] = b"NOPE"
        with pytest.raises(svc.FrameError) as ei:
            svc.decode_frame(bytes(buf))
        assert ei.value.code == svc.ERR_MALFORMED

    def test_future_version_is_typed_bad_version(self):
        buf = bytearray(svc.encode_frame(svc.FT_REQ)[4:])
        buf[4] = svc.VERSION + 1
        with pytest.raises(svc.FrameError) as ei:
            svc.decode_frame(bytes(buf))
        assert ei.value.code == svc.ERR_BAD_VERSION

    def test_every_short_header_prefix_is_typed_malformed(self):
        whole = svc.encode_frame(svc.FT_REQ, payload=b"\x01" * 8)[4:]
        for cut in range(svc.HEADER_BYTES):
            with pytest.raises(svc.FrameError) as ei:
                svc.decode_frame(whole[:cut])
            assert ei.value.code == svc.ERR_MALFORMED, cut

    def test_req_payload_bytes_pins_the_wire_cost(self):
        for n in (1, 7, 64, 4096):
            assert svc.req_payload_bytes(svc.KIND_COMPACT, n) == 128 * n
            assert svc.req_payload_bytes(svc.KIND_INDEXED, n) == 100 * n
        with pytest.raises(svc.FrameError):
            svc.req_payload_bytes(9, 1)

    def test_parse_address_schemes(self):
        assert svc.parse_address("unix:///tmp/x.sock") == (
            "unix", "/tmp/x.sock"
        )
        assert svc.parse_address("tcp://127.0.0.1:7777") == (
            "tcp", ("127.0.0.1", 7777)
        )
        assert svc.parse_address("/tmp/bare.sock") == (
            "unix", "/tmp/bare.sock"
        )
        # an unrecognized scheme must not fall through to the bare-path
        # branch just because it contains slashes
        for bad in ("ftp://nope", "grpc://host:1", "unix://", "tcp://x",
                    "tcp://x:notaport", "justaname"):
            with pytest.raises(ValueError):
                svc.parse_address(bad)

    def test_error_payload_round_trip(self):
        for code, msg in [
            (svc.ERR_MALFORMED, "short frame"),
            (svc.ERR_STALE_GENERATION, "gen 3 != 4"),
            (svc.ERR_OVERSIZE, "too wide — 8193 lanes"),
            (svc.ERR_INTERNAL, ""),
        ]:
            got_code, got_msg = svc.decode_error(svc.encode_error(code, msg))
            assert (got_code, got_msg) == (code, msg)
        # a truncated error frame still yields a typed pair
        code, _ = svc.decode_error(b"\x01")
        assert code == svc.ERR_INTERNAL


# ---------------------------------------------------------------------------
# packing: the RPC payload IS the PR 13 wire format
# ---------------------------------------------------------------------------


class TestPackItems:
    @pytest.mark.parametrize("n", [1, 3, 8, 65])
    def test_compact_matches_prepare_batch_compact(self, n):
        from cometbft_tpu.crypto.tpu import ed25519_batch as eb

        items = _batch(n, tag=b"pack-%d" % n)
        wire, valid = svc.pack_items_compact(items)
        assert wire.shape == (128, n) and wire.dtype == np.uint8
        assert valid.all()
        ref_wire, ref_valid = eb.prepare_batch_compact(
            [svc._pk_bytes(pk) for pk, _, _ in items],
            [m for _, m, _ in items],
            [s for _, _, s in items],
        )
        np.testing.assert_array_equal(wire, ref_wire)
        np.testing.assert_array_equal(valid, np.asarray(ref_valid))

    def test_indexed_is_100_bytes_per_lane(self):
        items = _batch(6, tag=b"pack-idx")
        index = {svc._pk_bytes(pk): i for i, (pk, _, _) in enumerate(items)}
        rsh, idx, valid = svc.pack_items_indexed(items, index)
        assert rsh.shape == (96, 6) and rsh.dtype == np.uint8
        assert idx.dtype == np.int32 and list(idx) == list(range(6))
        assert valid.all()
        assert (rsh.nbytes + idx.nbytes) / len(items) == 100.0
        # rsh rows are the compact wire minus the 32 pubkey rows
        wire, _ = svc.pack_items_compact(items)
        np.testing.assert_array_equal(rsh, wire[32:])


class TestCachingRowVerifier:
    def test_parity_and_memoization(self):
        items = _batch(5, tag=b"cache", bad=(1, 3))
        wire, _ = svc.pack_items_compact(items)
        v = svc.CachingRowVerifier(max_entries=16)
        mask = v(wire)
        assert list(mask) == _expected(items)
        assert v.misses == 5 and v.hits == 0
        # repeats are dict hits, verdicts unchanged
        mask2 = v(wire)
        assert list(mask2) == list(mask)
        assert v.misses == 5 and v.hits == 5


# ---------------------------------------------------------------------------
# live service harness
# ---------------------------------------------------------------------------


class _Daemon:
    """One scheduler + service on a fresh Unix socket, with an optional
    gate the row verifier blocks on (freezing the 'device pool' so
    requests are provably in flight when chaos strikes)."""

    def __init__(self, tag, coalesce=True, gate=None, flush_us=200,
                 auth_key=None):
        self.gate = gate
        inner = svc.host_row_verifier()

        def verifier(rows):
            if gate is not None:
                gate.wait(20)
            return inner(rows)

        self.sched = VerifyScheduler(
            spec="cpu", flush_us=flush_us, lane_budget=256,
            max_queue=256, qos="off", row_verifier=verifier,
        )
        self.path = "/tmp/cbft-test-svc-%s-%d.sock" % (tag, os.getpid())
        self.address = "unix://" + self.path
        self.service = svc.VerifyService(
            self.sched, self.address, coalesce=coalesce,
            row_verifier=verifier, auth_key=auth_key,
        )
        self.sched.start()
        self.service.start()
        self.clients = []

    def client(self, tenant, timeout_ms=15_000, auth_key=None,
               node_id=None, retry_s=0.05):
        c = svc.RemoteVerifier(
            self.address, tenant=tenant, timeout_ms=timeout_ms,
            retry_s=retry_s, auth_key=auth_key, node_id=node_id,
        )
        self.clients.append(c)
        return c

    def stop(self):
        for c in self.clients:
            c.close()
        self.service.stop()
        self.sched.stop()
        try:
            os.unlink(self.path)
        except OSError:
            pass


@pytest.fixture
def daemon(request):
    d = _Daemon(request.node.name.replace("[", "-").replace("]", ""))
    yield d
    d.stop()


class TestServiceEndToEnd:
    def test_verdicts_and_bytes_per_lane(self, daemon):
        items = _batch(9, tag=b"e2e", bad=(0, 4))
        fut = daemon.client("t0").submit(items, subsystem="consensus")
        ok, mask = fut.result(timeout=30)
        assert not ok and mask == _expected(items)
        assert not fut.rejected
        snap = daemon.service.snapshot()
        assert snap["bytes_per_lane"]["compact"] == 128.0
        assert snap["lanes"]["compact"] == 9
        assert snap["tenants"] == ["t0"]

    def test_empty_submit_never_touches_the_wire(self, daemon):
        ok, mask = daemon.client("t0").submit([]).result(timeout=5)
        assert ok and mask == []
        assert daemon.service.snapshot()["frames"].get("req", 0) == 0

    def test_cross_client_demux(self, daemon):
        """N clients submit interleaved batches with per-client corrupt
        lanes; every future must carry exactly its OWN verdicts even
        when one coalesced flush served several clients."""
        n_clients, lanes, rounds = 4, 8, 3
        clients = [daemon.client("demux%d" % i) for i in range(n_clients)]
        batches = [
            [
                _batch(lanes, tag=b"demux-%d-%d" % (c, r), bad=(c % lanes,))
                for r in range(rounds)
            ]
            for c in range(n_clients)
        ]
        results = [[None] * rounds for _ in range(n_clients)]
        start = threading.Barrier(n_clients)

        def run(c):
            start.wait(10)
            futs = [
                clients[c].submit(batches[c][r], subsystem="consensus")
                for r in range(rounds)
            ]
            for r, f in enumerate(futs):
                results[c][r] = f.result(timeout=30)

        threads = [
            threading.Thread(target=run, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for c in range(n_clients):
            want = [i != c % lanes for i in range(lanes)]
            for r in range(rounds):
                ok, mask = results[c][r]
                assert not ok and mask == want, (c, r, mask)
        snap = daemon.service.snapshot()
        assert snap["lanes"]["compact"] == n_clients * lanes * rounds
        assert snap["bytes_per_lane"]["compact"] == 128.0
        assert sorted(snap["disconnects"]) == []


class TestDisconnectContainment:
    def test_kill_mid_flight_contains_to_one_tenant(self):
        gate = threading.Event()
        d = _Daemon("kill", gate=gate)
        try:
            victim = d.client("victim")
            survivor = d.client("survivor")
            vic_items = _batch(6, tag=b"vic", bad=(2,))
            sur_items = _batch(6, tag=b"sur", bad=(5,))
            # park both requests against the gated pool
            vic_fut = victim.submit(vic_items, subsystem="blocksync")
            sur_fut = survivor.submit(sur_items, subsystem="blocksync")
            deadline = time.monotonic() + 10
            while (d.service.pending_requests() < 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert d.service.pending_requests() >= 2
            # sever the victim's socket abruptly, mid-flight
            victim.kill_connection()
            ok, mask = vic_fut.result(timeout=30)
            # distinct reason + ground-truth verdict via local fallback
            assert vic_fut.reason == "disconnected"
            assert not ok and mask == _expected(vic_items)
            assert victim.stats().get("disconnected", 0) >= 1
            # thaw the pool: the survivor's request — same coalesced
            # flush — still completes correctly
            gate.set()
            ok, mask = sur_fut.result(timeout=30)
            assert not ok and mask == _expected(sur_items)
            assert getattr(sur_fut, "reason", None) is None
            # the server metered the severed tenant, and only it
            deadline = time.monotonic() + 10
            while (not d.service.snapshot()["disconnects"]
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            disc = d.service.snapshot()["disconnects"]
            assert disc.get("victim", 0) >= 1
            assert "survivor" not in disc
            # the victim reconnects on its next submit (once its
            # retry_s backoff window has passed)
            time.sleep(0.2)
            ok, mask = victim.submit(
                _batch(3, tag=b"vic2"), subsystem="blocksync"
            ).result(timeout=30)
            assert ok and mask == [True] * 3
            assert victim.stats().get("connects", 0) >= 2
            assert victim.stats().get("remote_ok", 0) >= 1
        finally:
            gate.set()
            d.stop()


# ---------------------------------------------------------------------------
# malformed / truncated / oversized frames: typed refusal, accept
# loop survives
# ---------------------------------------------------------------------------


def _raw_conn(daemon):
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(5.0)
    s.connect(daemon.path)
    frame = _read_frame(s)  # server greets with HELLO
    assert frame.ftype == svc.FT_HELLO
    return s


def _read_frame(s):
    head = b""
    while len(head) < 4:
        chunk = s.recv(4 - len(head))
        if not chunk:
            return None
        head += chunk
    (length,) = _LEN.unpack(head)
    buf = b""
    while len(buf) < length:
        chunk = s.recv(length - len(buf))
        if not chunk:
            return None
        buf += chunk
    return svc.decode_frame(buf)


def _expect_err(daemon, data, code):
    s = _raw_conn(daemon)
    try:
        s.sendall(data)
        frame = _read_frame(s)
        assert frame is not None and frame.ftype == svc.FT_ERR
        got, msg = svc.decode_error(frame.payload)
        assert got == code, (svc.ERR_NAMES.get(got, got), msg)
        return frame
    finally:
        s.close()


class TestFrameFuzz:
    def test_truncation_at_every_offset_never_kills_the_accept_loop(
        self, daemon
    ):
        items = _batch(2, tag=b"fuzz")
        wire, _ = svc.pack_items_compact(items)
        for ctx in (None, (0x1234ABCD, 0x77, True)):
            # both header shapes: the v1 wire and the v2 extended header
            # carrying a trace-context extension block
            whole = svc.encode_frame(
                svc.FT_REQ, kind=svc.KIND_COMPACT, req_id=1, n_lanes=2,
                payload=wire.tobytes(), trace_ctx=ctx,
            )
            for cut in range(1, len(whole)):
                s = _raw_conn(daemon)
                s.sendall(whole[:cut])
                s.close()
        # the service survived all of it: a real client still verifies
        ok, mask = daemon.client("after-fuzz").submit(
            items, subsystem="consensus"
        ).result(timeout=30)
        assert ok and mask == [True, True]
        assert daemon.service.snapshot()["connections"] <= 2

    def test_bad_magic_is_refused_typed(self, daemon):
        buf = bytearray(svc.encode_frame(svc.FT_REQ, n_lanes=0))
        buf[4:8] = b"EVIL"
        _expect_err(daemon, bytes(buf), svc.ERR_MALFORMED)

    def test_future_version_is_refused_typed(self, daemon):
        buf = bytearray(svc.encode_frame(svc.FT_REQ, n_lanes=0))
        buf[8] = svc.VERSION + 3
        _expect_err(daemon, bytes(buf), svc.ERR_BAD_VERSION)

    def test_unknown_frame_type_is_refused_typed(self, daemon):
        _expect_err(
            daemon, svc.encode_frame(250), svc.ERR_MALFORMED,
        )

    def test_server_only_frame_type_is_refused_typed(self, daemon):
        _expect_err(
            daemon, svc.encode_frame(svc.FT_RESP), svc.ERR_MALFORMED,
        )

    def test_bad_qos_class_is_refused_typed(self, daemon):
        wire, _ = svc.pack_items_compact(_batch(1, tag=b"class"))
        _expect_err(daemon, svc.encode_frame(
            svc.FT_REQ, qclass=0x77, n_lanes=1, payload=wire.tobytes(),
        ), svc.ERR_BAD_CLASS)

    def test_payload_size_mismatch_is_refused_typed(self, daemon):
        _expect_err(daemon, svc.encode_frame(
            svc.FT_REQ, n_lanes=3, payload=b"\x00" * 100,
        ), svc.ERR_MALFORMED)

    def test_zero_and_oversize_lanes_are_refused_typed(self, daemon):
        _expect_err(daemon, svc.encode_frame(
            svc.FT_REQ, n_lanes=0,
        ), svc.ERR_MALFORMED)
        n = daemon.service.snapshot()["max_lanes"] + 1
        _expect_err(daemon, svc.encode_frame(
            svc.FT_REQ, n_lanes=n, payload=b"",
        ), svc.ERR_MALFORMED)

    def test_ragged_register_payload_is_refused_typed(self, daemon):
        _expect_err(daemon, svc.encode_frame(
            svc.FT_REGISTER, n_lanes=1, payload=b"\x01" * 33,
        ), svc.ERR_MALFORMED)

    def test_oversize_length_prefix_is_refused_typed(self, daemon):
        snap = daemon.service.snapshot()
        too_big = svc.max_frame_bytes(snap["max_lanes"]) + 1
        s = _raw_conn(daemon)
        try:
            s.sendall(_LEN.pack(too_big))
            frame = _read_frame(s)
            assert frame is not None and frame.ftype == svc.FT_ERR
            code, _ = svc.decode_error(frame.payload)
            assert code == svc.ERR_OVERSIZE
        finally:
            s.close()

    def test_auth_and_drain_frame_truncation_never_kills_the_accept_loop(
        self, daemon
    ):
        """The PR 20 frame types get the same truncation treatment as
        FT_REQ: every prefix of an AUTH / DRAINING / AUTH_OK frame, cut
        mid-header and mid-payload, must leave the accept loop alive."""
        shapes = [
            svc.encode_frame(
                svc.FT_AUTH,
                payload=b"\x5a" * svc.AUTH_MAC_BYTES + b"node-x",
            ),
            svc.encode_frame(svc.FT_DRAINING),
            svc.encode_frame(svc.FT_AUTH_OK, req_id=9),
        ]
        for whole in shapes:
            for cut in range(1, len(whole)):
                s = _raw_conn(daemon)
                s.sendall(whole[:cut])
                s.close()
        items = _batch(2, tag=b"fuzz-auth")
        ok, mask = daemon.client("after-auth-fuzz").submit(
            items, subsystem="consensus"
        ).result(timeout=30)
        assert ok and mask == [True, True]

    def test_client_sent_draining_and_auth_ok_are_refused_typed(
        self, daemon
    ):
        _expect_err(
            daemon, svc.encode_frame(svc.FT_DRAINING), svc.ERR_MALFORMED,
        )
        _expect_err(
            daemon, svc.encode_frame(svc.FT_AUTH_OK), svc.ERR_MALFORMED,
        )

    def test_connection_survives_a_typed_refusal(self, daemon):
        """Per-request refusals don't kill the connection: a good frame
        on the SAME socket still gets its verdict."""
        items = _batch(2, tag=b"survive")
        wire, _ = svc.pack_items_compact(items)
        s = _raw_conn(daemon)
        try:
            s.sendall(svc.encode_frame(
                svc.FT_REQ, req_id=7, n_lanes=5, payload=b"\x00" * 12,
            ))
            frame = _read_frame(s)
            assert frame.ftype == svc.FT_ERR and frame.req_id == 7
            s.sendall(svc.encode_frame(
                svc.FT_REQ, req_id=8, n_lanes=2, payload=wire.tobytes(),
            ))
            deadline = time.monotonic() + 20
            frame = _read_frame(s)
            assert frame is not None and frame.ftype == svc.FT_RESP
            assert frame.req_id == 8 and time.monotonic() < deadline
            assert frame.payload[0] == svc.ST_OK
            bits = np.unpackbits(
                np.frombuffer(frame.payload[1:], np.uint8),
                bitorder="little",
            )[:2]
            assert list(bits.astype(bool)) == [True, True]
        finally:
            s.close()


# ---------------------------------------------------------------------------
# keystore generation handshake: stale -> compact fallback -> resync
# -> indexed again
# ---------------------------------------------------------------------------


class TestGenerationHandshake:
    def test_stale_client_falls_back_then_upgrades_after_resync(self):
        d = _Daemon("gen")
        try:
            from cometbft_tpu.crypto.tpu import keystore

            store = keystore.default_store()
            client = d.client("valclient")
            items = _batch(8, tag=b"gen", bad=(3,))
            pks = [svc._pk_bytes(pk) for pk, _, _ in items]
            want = _expected(items)

            # register -> covered submits ship 100 B/lane indexed rows
            client.register_valset(pks)
            assert client.stats().get("registrations", 0) == 1
            ok, mask = client.submit(
                items, subsystem="consensus"
            ).result(timeout=30)
            assert not ok and mask == want
            snap = d.service.snapshot()
            assert snap["lanes"].get("indexed", 0) == 8
            assert snap["bytes_per_lane"]["indexed"] == 100.0

            # another valset lands beside the client's: the id names
            # keys, so nobody's registration goes stale (PR 30)
            other = [
                ed.gen_priv_key_from_secret(b"gen-bump-%d" % i)
                .pub_key().bytes()
                for i in range(4)
            ]
            import hashlib
            other_id = hashlib.sha256(b"".join(other)).digest()[:16]
            gen = store.generation()
            store.register(other_id, other)
            assert store.generation() == gen
            fut = client.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=30)
            assert getattr(fut, "reason", None) is None
            assert not ok and mask == want
            assert client.stats().get("registrations", 0) == 1
            assert d.service.snapshot()["lanes"]["indexed"] == 16

            # the key space changes behind the client's back: a valset
            # LEAVES the store, bumping the generation
            assert store.invalidate(other_id) == 1
            assert store.generation() == gen + 1

            # stale submit: the server REFUSES the indexed frame (typed
            # stale_generation, stale_drops metered) and the client sends
            # the same lanes again as compact rows: served by the daemon,
            # no local fallback
            fut = client.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=30)
            assert getattr(fut, "reason", None) is None
            assert not ok and mask == want
            assert client.stats().get("stale", 0) >= 1
            assert client.stats().get("stale_resends", 0) == 1
            snap = d.service.snapshot()
            assert snap["stale_drops"] >= 1
            assert snap["errors"].get("stale_generation", 0) >= 1
            assert snap["lanes"].get("compact", 0) == 8

            # next submit resyncs (re-register at the new generation)
            # and goes indexed again — never stuck on the fallback
            fut = client.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=30)
            assert getattr(fut, "reason", None) is None
            assert not ok and mask == want
            assert client.stats().get("registrations", 0) == 2
            snap = d.service.snapshot()
            assert snap["lanes"]["indexed"] == 24
            assert snap["bytes_per_lane"]["indexed"] == 100.0
            assert all(v <= 128.0 for v in snap["bytes_per_lane"].values())
            # the books a fleet's cell reads: every response timed on
            # both sides, every frame counted
            assert snap["served"] == 4 and snap["served_s"] > 0
            assert snap["keystore"]["generation"] == gen + 1
            stats = client.snapshot()
            assert stats["stats"]["req_frames"] == 5
            assert stats["stats"]["register_frames"] == 2
            assert stats["stats"]["rtts"] == 4
            assert stats["rtt_s"] >= snap["served_s"]
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# authenticated sessions (PR 20): HMAC challenge-response on HELLO
# ---------------------------------------------------------------------------


_KEY = b"test-fleet-key-20"


class TestAuthSessions:
    def test_wrong_key_is_refused_typed_with_no_retry_storm(self):
        d = _Daemon("auth-wrong", auth_key=_KEY)
        try:
            c = d.client(
                "evil", timeout_ms=4000, auth_key=b"not-the-key",
                node_id="evil", retry_s=0.2,
            )
            items = _batch(4, tag=b"auth-w", bad=(1,))
            want = _expected(items)
            fut = c.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=20)
            # ground truth via the local CPU rung, typed reason — never
            # the failover rung (the whole fleet shares the key)
            assert fut.reason == "unauthorized"
            assert not ok and mask == want
            assert c.stats().get("unauthorized", 0) >= 1
            assert "unauthorized" not in svc.FAILOVER_REASONS
            # a burst of submits must not hammer the daemon: auth
            # refusals escalate the reconnect backoff
            for _ in range(10):
                f = c.submit(items, subsystem="consensus")
                f.result(timeout=20)
                assert f.reason == "unauthorized"
            assert c.stats().get("connect_attempts", 0) <= 4
            snap = d.service.snapshot()
            assert snap["auth_rejects"] >= 1
            # refused work never reached the scheduler
            assert sum(snap["lanes"].values()) == 0
            panel = snap.get("tenants_panel", {})
            assert (panel.get("evil", {}) or {}).get("requests", 0) == 0
        finally:
            d.stop()

    def test_right_key_tenant_is_the_authenticated_node_id(self):
        d = _Daemon("auth-right", auth_key=_KEY)
        try:
            # the CLIENT_HELLO tenant hint must not let a key holder
            # ride another tenant's quota: the authenticated id wins
            c = d.client(
                "pretender", auth_key=_KEY, node_id="node-7",
            )
            items = _batch(3, tag=b"auth-r", bad=(0,))
            fut = c.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=30)
            assert not ok and mask == _expected(items)
            assert getattr(fut, "reason", None) is None
            assert c.stats().get("auth_ok", 0) >= 1
            snap = d.service.snapshot()
            assert snap["auth_ok"] >= 1
            panel = snap["tenants_panel"]
            assert panel.get("node-7", {}).get("requests", 0) >= 1
            assert "pretender" not in panel
        finally:
            d.stop()

    def test_keyless_client_against_auth_server_is_refused_typed(self):
        d = _Daemon("auth-keyless", auth_key=_KEY)
        try:
            c = d.client("naive")
            items = _batch(3, tag=b"auth-k")
            fut = c.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=20)
            assert fut.reason == "unauthorized"
            assert ok and mask == [True] * 3
            assert c.stats().get("err_unauthorized", 0) >= 1
            assert sum(d.service.snapshot()["lanes"].values()) == 0
        finally:
            d.stop()

    def test_keyed_client_against_open_server_interops(self, daemon):
        # v1/no-auth interop: the open server's HELLO carries no auth
        # flag, so the keyed client skips the handshake and just works
        c = daemon.client("keyed", auth_key=_KEY, node_id="keyed-1")
        items = _batch(3, tag=b"interop", bad=(2,))
        fut = c.submit(items, subsystem="consensus")
        ok, mask = fut.result(timeout=30)
        assert not ok and mask == _expected(items)
        assert getattr(fut, "reason", None) is None


# ---------------------------------------------------------------------------
# graceful drain (PR 20): in-flight answered, new work refused typed
# ---------------------------------------------------------------------------


class TestDrain:
    def test_drain_answers_inflight_and_refuses_new_typed(self):
        gate = threading.Event()
        d = _Daemon("drain", gate=gate)
        try:
            holder = d.client("holder")
            items = _batch(5, tag=b"drain", bad=(2,))
            want = _expected(items)
            fut = holder.submit(items, subsystem="consensus")
            deadline = time.monotonic() + 10
            while (d.service.pending_requests() < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert d.service.pending_requests() >= 1
            d.service.drain()
            assert d.service.snapshot()["draining"] is True
            # NEW work is refused with the typed ST_DRAINING status and
            # resolves on the caller's local CPU rung, distinct reason
            late = d.client("late")
            f2 = late.submit(items, subsystem="consensus")
            ok2, mask2 = f2.result(timeout=20)
            assert f2.reason == "draining"
            assert not ok2 and mask2 == want
            # the parked in-flight request is still answered — drain is
            # graceful, not a guillotine
            gate.set()
            ok, mask = fut.result(timeout=30)
            assert getattr(fut, "reason", None) is None
            assert not ok and mask == want
            snap = d.service.snapshot()
            assert snap["drain_refusals"] >= 1
        finally:
            gate.set()
            d.stop()

    def test_drain_broadcast_reaches_connected_clients(self):
        d = _Daemon("drain-bcast")
        try:
            c = d.client("watcher")
            ok, _ = c.submit(
                _batch(2, tag=b"bcast"), subsystem="consensus"
            ).result(timeout=30)
            assert ok
            assert not c.server_draining
            d.service.drain()
            deadline = time.monotonic() + 10
            while (not c.server_draining
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert c.server_draining
            assert c.stats().get("server_draining", 0) >= 1
            assert c.snapshot()["server_draining"] is True
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# reconnect backoff (PR 20): a dead daemon is not hammered
# ---------------------------------------------------------------------------


class TestReconnectBackoff:
    def test_dead_endpoint_backoff_bounds_connect_attempts(self):
        c = svc.RemoteVerifier(
            "unix:///tmp/cbft-test-noexist-%d.sock" % os.getpid(),
            tenant="lonely", timeout_ms=2000, retry_s=0.2,
            retry_cap_s=1.0,
        )
        try:
            items = _batch(2, tag=b"backoff")
            want = _expected(items)
            for _ in range(10):
                f = c.submit(items, subsystem="consensus")
                ok, mask = f.result(timeout=10)
                assert f.reason == "disconnected"
                assert mask == want
            # ten rapid submits, at most a few real connect() calls:
            # the capped-exponential window swallowed the rest
            assert 1 <= c.stats().get("connect_attempts", 0) <= 4
            snap = c.snapshot()
            assert snap["connected"] is False
            r = snap["reconnect"]
            assert r["connect_fails"] >= 1
            assert r["last_backoff_s"] > 0
            assert r["retry_base_s"] == 0.2
            assert r["retry_cap_s"] == 1.0
        finally:
            c.close()


# ---------------------------------------------------------------------------
# bench history: the service stage's guard directions
# ---------------------------------------------------------------------------


class TestServiceBenchDirections:
    def test_coalesce_gain_and_p99_directions(self):
        import importlib.util

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "bench_history_service_test",
            os.path.join(repo, "tools", "bench_history.py"),
        )
        bh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bh)
        for leaf in ("service_coalesce_gain",
                     "stages.service.service_coalesce_gain"):
            assert bh.direction(leaf) == bh.HIGHER_IS_BETTER, leaf
        for leaf in ("service_p99_ms", "service_isolated_p99_ms",
                     "stages.service.service_p99_ms"):
            assert bh.direction(leaf) == bh.LOWER_IS_BETTER, leaf
        # throughput keeps the generic per-second rule
        assert (bh.direction("service_coalesced_sigs_per_sec")
                == bh.HIGHER_IS_BETTER)
        # booleans stay directionless
        assert bh.direction("service_coalesce_gain_ok") is None
