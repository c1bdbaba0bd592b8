"""Daemon kill/restart recovery (PR 18 satellite): a verifyd crash
mid-storm must cost at most the in-flight requests — resolved locally
with the distinct ``disconnected`` reason and ground-truth verdicts —
and the client must walk disconnected -> local fallback -> reconnect ->
re-register -> indexed resume against the restarted daemon, all by
itself. Runs over a real Unix socket on the virtual CPU mesh
(conftest.py); the restarted daemon's keystore is cold (invalidate),
so the walk exercises the generation handshake too."""

import os
import threading
import time

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import service as svc
from cometbft_tpu.crypto.scheduler import VerifyScheduler
from cometbft_tpu.crypto.tpu import keystore


def _batch(n, tag=b"rst", bad=()):
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


class _Epoch:
    """One daemon lifetime: scheduler + service on a shared socket
    path, pool gated so requests are provably in flight at the kill."""

    def __init__(self, path, gate):
        inner = svc.host_row_verifier()

        def verifier(rows):
            gate.wait(20)
            return inner(rows)

        self.sched = VerifyScheduler(
            spec="cpu", flush_us=200, lane_budget=256, max_queue=256,
            qos="off", row_verifier=verifier,
        )
        self.service = svc.VerifyService(
            self.sched, "unix://" + path, coalesce=True,
            row_verifier=verifier,
        )
        self.sched.start()
        self.service.start()

    def stop(self):
        self.service.stop()
        self.sched.stop()


class TestDaemonRestartRecovery:
    def test_kill_restart_walks_reconnect_reregister_indexed(self):
        path = "/tmp/cbft-test-restart-%d.sock" % os.getpid()
        gate = threading.Event()
        gate.set()
        store = keystore.default_store()
        store.invalidate()
        epoch = _Epoch(path, gate)
        client = svc.RemoteVerifier(
            "unix://" + path, tenant="restart", timeout_ms=15_000,
            retry_s=0.05,
        )
        items = _batch(8, bad=(2,))
        pks = [svc._pk_bytes(pk) for pk, _, _ in items]
        want = _expected(items)
        try:
            # epoch 1: registered valset, indexed wire, remote verdicts
            client.register_valset(pks)
            ok, mask = client.submit(
                items, subsystem="consensus"
            ).result(timeout=30)
            assert not ok and mask == want
            s = client.stats()
            assert s.get("connects", 0) == 1
            assert s.get("registrations", 0) == 1
            remote_ok_e1 = s.get("remote_ok", 0)
            assert remote_ok_e1 >= 1
            assert epoch.service.snapshot()["lanes"].get("indexed", 0) == 8

            # freeze the pool, park a request, then kill the daemon
            # out from under it
            gate.clear()
            fut = client.submit(items, subsystem="consensus")
            deadline = time.monotonic() + 10
            while (epoch.service.pending_requests() < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert epoch.service.pending_requests() >= 1
            # sever the wire first (the crash), THEN release the dead
            # epoch's pool so its scheduler can drain and join fast
            epoch.service.stop()
            gate.set()
            epoch.sched.stop()

            # the in-flight request resolves LOCALLY with the distinct
            # reason and ground-truth verdicts — never an error, never
            # a wrong verdict
            ok, mask = fut.result(timeout=30)
            assert fut.reason == "disconnected"
            assert not ok and mask == want
            assert client.stats().get("disconnected", 0) >= 1

            # restart on the same socket with a COLD keystore: the
            # restarted daemon knows nothing about the client's valset
            store.invalidate()
            epoch = _Epoch(path, gate)
            time.sleep(0.2)  # let the client's retry backoff lapse

            # the client walks back unaided: reconnect -> re-register
            # (generation handshake against the cold store) -> indexed.
            # An interim submit is served as compact rows (the stale
            # resend, PR 30) or resolves via the disconnect fallback —
            # with correct verdicts — but the walk must converge.
            last = None
            for _ in range(3):
                last = client.submit(items, subsystem="consensus")
                ok, mask = last.result(timeout=30)
                assert not ok and mask == want  # verdicts exact throughout
                reason = getattr(last, "reason", None)
                assert reason in (None, "stale", "disconnected"), reason
                if reason is None and epoch.service.snapshot()["lanes"].get(
                        "indexed", 0) >= 8:
                    break
            assert getattr(last, "reason", None) is None, (
                "client never resumed remote verification", client.stats()
            )
            s = client.stats()
            assert s.get("connects", 0) >= 2
            assert s.get("registrations", 0) >= 2
            assert s.get("remote_ok", 0) > remote_ok_e1
            assert s.get("resync_failed", 0) == 0
            # the resumed wire is indexed on the NEW daemon
            assert epoch.service.snapshot()["lanes"].get("indexed", 0) >= 8
        finally:
            gate.set()
            client.close()
            epoch.stop()
            store.invalidate()
            try:
                os.unlink(path)
            except OSError:
                pass
