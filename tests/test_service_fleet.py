"""What a fleet of light clients forces on verifyd (PR 30): a row flush
is cut as the keyed route cuts its flushes, many registered validator
sets stale nobody, and what does stale a client still refuses its
indexed frames."""

import hashlib
import os
import threading

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import service as svc
from cometbft_tpu.crypto.scheduler import VerifyScheduler


# -- dispatch_rows on the keyed route's launch rule ------------------------


@pytest.fixture()
def launches(monkeypatch):
    """Every mesh.launch_stream call's (kernel, [(start, end, size)]),
    with nothing launched."""
    from cometbft_tpu.crypto.tpu import mesh

    seen = []

    def stream(kernel, chunks, build, n, **kw):
        seen.append((kernel, [tuple(c[:3]) for c in chunks]))
        return np.zeros(n, bool), {"chunks": 0}

    monkeypatch.setattr(mesh, "launch_stream", stream)
    monkeypatch.setattr(mesh, "n_devices", lambda: 1)
    monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
    return seen


@pytest.mark.parametrize("n,want", [
    (51, [(0, 51, 64)]),
    (101, [(0, 101, 128)]),
    (2048, [(0, 2048, 2048)]),
    (3000, [(0, 952, 1024), (952, 3000, 2048)]),
    (3232, [(0, 1184, 2048), (1184, 3232, 2048)]),
    (6464, [(0, 320, 1024), (320, 2368, 2048), (2368, 4416, 2048),
            (4416, 6464, 2048)]),
])
def test_a_row_flush_is_cut_as_the_keyed_route_cuts_it(launches, n, want):
    from cometbft_tpu.crypto.tpu import aot, mesh
    from cometbft_tpu.crypto.tpu import ed25519_batch as eb

    svc.dispatch_rows(np.zeros((128, n), np.uint8))
    mesh.dispatch_batch(
        eb.verify_kernel_compact, [np.zeros((128, n), np.uint8)], n,
        eb._MAX_CHUNK, eb._MIN_PAD, launch=eb._LAUNCH_LANES,
    )
    (rows_kernel, rows), (keyed_kernel, keyed) = launches
    assert rows_kernel is keyed_kernel is eb.verify_kernel_compact
    assert rows == keyed == want
    # every shape is one the warm ladder or the small buckets hold, and
    # none is above the launch the kernel is registered with
    ladder = set(aot.bucket_ladder(floor=eb._MIN_PAD, cap=eb._LAUNCH_LANES))
    assert {size for _, _, size in rows} <= ladder
    assert max(size for _, _, size in rows) <= eb._LAUNCH_LANES


# -- the key store under a fleet -------------------------------------------


class _Daemon:
    def __init__(self, tag):
        verifier = svc.host_row_verifier()
        self.sched = VerifyScheduler(spec="cpu", flush_us=200,
                                     row_verifier=verifier)
        self.path = "/tmp/cbft-test-fleet-%s-%d.sock" % (tag, os.getpid())
        self.service = svc.VerifyService(
            self.sched, "unix://" + self.path, row_verifier=verifier)
        self.sched.start()
        self.service.start()
        self.clients = []

    def client(self, tenant):
        c = svc.RemoteVerifier("unix://" + self.path, tenant=tenant,
                               timeout_ms=60_000)
        self.clients.append(c)
        return c

    def stop(self):
        for c in self.clients:
            c.close()
        self.service.stop()
        self.sched.stop()


@pytest.fixture()
def daemon(request):
    from cometbft_tpu.crypto.tpu import keystore, topology

    keystore.default_store().invalidate()
    d = _Daemon(request.node.name[:24].replace("[", "-").replace("]", ""))
    yield d
    d.stop()
    keystore.default_store().invalidate()
    topo = topology.default_topology()
    for i in range(len(topo)):
        topo.set_quarantined(i, False)


def _chain(tag, n=4):
    keys = [ed.gen_priv_key_from_secret(b"%s-%d" % (tag, i))
            for i in range(n)]
    items = []
    for i, k in enumerate(keys):
        msg = b"%s header %d" % (tag, i)
        items.append((k.pub_key(), msg, k.sign(msg)))
    return [k.pub_key().bytes() for k in keys], items


def test_eight_sets_and_32_clients_settle_to_zero_register_frames(daemon):
    """Each client registers its chain's set once; from the second round
    on no REGISTER frame is sent, no frame is refused stale and every
    lane arrives indexed."""
    from cometbft_tpu.crypto.tpu import keystore

    chains = [_chain(b"fleet-%d" % c) for c in range(8)]
    split = [12, 6, 4, 3, 2, 2, 2, 1]
    chain_of = [c for c, n in enumerate(split) for _ in range(n)]
    clients = [daemon.client(f"light-{i}") for i in range(32)]
    errors = []

    def round_(register):
        def run(i):
            try:
                pks, items = chains[chain_of[i]]
                if register:
                    clients[i].register_valset(pks)
                fut = clients[i].submit(items, subsystem="light")
                ok, _ = fut.result(timeout=60)
                assert ok and getattr(fut, "reason", None) is None
            except BaseException as exc:  # noqa: BLE001 - raised below
                errors.append(exc)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors

    round_(register=True)
    first = daemon.service.snapshot()
    assert first["frames"]["register"] == 32
    for _ in range(3):
        round_(register=False)
    snap = daemon.service.snapshot()
    assert snap["frames"]["register"] == 32, "a client registered again"
    assert snap["frames"]["req"] - first["frames"]["req"] == 96
    assert snap["stale_drops"] == 0 and not snap["errors"]
    assert snap["lanes"].get("compact", 0) == first["lanes"].get("compact", 0)
    assert snap["lanes"]["indexed"] - first["lanes"]["indexed"] == 96 * 4
    store = keystore.default_store().snapshot()
    assert len(store["entries"]) == 8
    assert snap["keystore"]["evictions"] == 0 or \
        snap["keystore"]["evictions"] == first["keystore"]["evictions"]
    assert sum(c.stats().get("registrations", 0) for c in clients) == 32


@pytest.mark.parametrize("what", ["invalidate", "topology"])
def test_what_stales_a_client_still_refuses_its_indexed_frame(daemon, what):
    """An entry leaving the store, or a topology bump, refuses the next
    indexed frame as stale; the lanes are served as compact rows and the
    client registers again for the frame after."""
    from cometbft_tpu.crypto.tpu import keystore, topology

    store = keystore.default_store()
    pks, items = _chain(b"stale-" + what.encode())
    other, _ = _chain(b"other-" + what.encode())
    client = daemon.client("light-0")
    client.register_valset(pks)
    daemon.client("light-1").register_valset(other)
    ok, _ = client.submit(items).result(timeout=60)
    assert ok
    if what == "invalidate":
        other_id = hashlib.sha256(b"".join(other)).digest()[:16]
        assert store.invalidate(other_id) == 1
    else:
        assert topology.default_topology().set_quarantined(0, True)
    before = daemon.service.snapshot()
    fut = client.submit(items)
    ok, mask = fut.result(timeout=60)
    assert ok and mask == [True] * 4
    assert getattr(fut, "reason", None) is None, "the local CPU answered"
    snap = daemon.service.snapshot()
    assert snap["stale_drops"] == before["stale_drops"] + 1
    assert snap["errors"]["stale_generation"] >= 1
    assert snap["lanes"]["compact"] - before["lanes"].get("compact", 0) == 4
    assert client.stats()["stale_resends"] == 1
    # the frame after: registered again, indexed again
    ok, _ = client.submit(items).result(timeout=60)
    assert ok
    assert client.stats()["registrations"] == 2
    after = daemon.service.snapshot()
    assert after["lanes"]["indexed"] - snap["lanes"]["indexed"] == 4


def test_a_lane_that_does_not_pack_takes_the_local_rung_on_a_stale_frame(
        daemon):
    """The resend ships what the indexed frame shipped; where that cannot
    be packed as compact rows the request resolves locally, reason kept."""
    from cometbft_tpu.crypto.tpu import keystore

    pks, items = _chain(b"stale-local")
    client = daemon.client("light-0")
    client.register_valset(pks)
    keystore.default_store().invalidate()
    real = svc.pack_items_compact

    def refuse(part):
        wire, valid = real(part)
        return wire, np.zeros_like(valid)

    svc.pack_items_compact = refuse
    try:
        fut = client.submit(items)
        ok, mask = fut.result(timeout=60)
    finally:
        svc.pack_items_compact = real
    assert ok and mask == [True] * 4
    assert fut.reason == "stale"
