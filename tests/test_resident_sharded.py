"""The resident commit path on several chips (ed25519_batch
verify_valset_resident over mesh.shard_plan's mesh), on the suite's
8-device virtual CPU mesh.

The per-lane mask is held to the benchmark's plain reference
(benchmark/lib/reference.py, which shares no code with the program) on a
validator set that is no multiple of the chip count and spans two
chunks, with one corrupted signature in every chip's slice of every
chunk, each at another offset, one absent lane and one 31-byte key: a
dropped, repeated or misordered shard changes the mask, which a
whole-commit verdict would not show. Then what the path leaves on
record: the shard plan it ran on, the stages' tags, the wire ledger's
real and padded lanes, and no executable missed on a second commit.
"""

import hashlib

import pytest

from benchmark.lib import reference
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.crypto.tpu import aot, ed25519_batch as eb, mesh, topology
from cometbft_tpu.libs import trace as tracelib

CAP = 256        # chunk cap: 316 validators are a chunk of 256 and one of 60
N = 316          # 39.5 x 8 chips
SHARDS = 8
# chunk 1: 32 lanes a chip; chunk 2 pads 60 lanes to 64: 8 lanes a chip,
# the last chip's slice half padding
CHUNKS = [(0, 256, 256), (256, 316, 64)]
ABSENT = 100
SHORT_KEY = 200


def _corrupted_lanes():
    """One lane in every chip's slice of every chunk, at an offset that
    differs from chip to chip."""
    lanes = []
    for start, end, size in CHUNKS:
        per = size // SHARDS
        for k in range(SHARDS):
            lane = start + k * per + (k * 5 + 3) % min(per, end - start - k * per)
            assert start + k * per <= lane < min(end, start + (k + 1) * per)
            lanes.append(lane)
    return lanes


def _commit(seed: int, height: int):
    """(pub keys, messages, signatures, the reference's mask)."""
    pks, msgs, sigs = [], [], []
    spoiled = set(_corrupted_lanes())
    assert len(spoiled) == 2 * SHARDS
    assert not spoiled & {ABSENT, SHORT_KEY}
    for i in range(N):
        priv = ed.gen_priv_key_from_secret(
            b"resident-sharded|%d|%d" % (seed, i))
        msg = b"precommit|h=%d|val=%d" % (height, i)
        sig = bytearray(priv.sign(msg))
        if i in spoiled:
            sig[(i * 7) % 32] ^= 1 << (i % 8)
        pk = priv.pub_key().bytes()
        pks.append(pk[:31] if i == SHORT_KEY else pk)
        msgs.append(None if i == ABSENT else msg)
        sigs.append(None if i == ABSENT else bytes(sig))
    present = [i for i in range(N) if i != ABSENT]
    verdicts = reference.verify_many(
        [(pks[i], msgs[i], sigs[i]) for i in present])
    want = [False] * N
    for i, ok in zip(present, verdicts):
        want[i] = ok
    return pks, msgs, sigs, want


@pytest.fixture()
def plane():
    """A process as a node with ``fault_domains = 0`` leaves it: a fault
    domain per visible device, the chunk cap configured, a wire ledger."""
    before = topology.default_topology()
    prev_ledger = wirelib.default_ledger()
    topology.set_default_topology(topology.DeviceTopology.detect())
    mesh.configure_chunk_cap(CAP)
    eb._keystore.invalidate()
    ledger = wirelib.WireLedger()
    wirelib.set_default_ledger(ledger)
    try:
        yield ledger
    finally:
        wirelib.set_default_ledger(prev_ledger)
        mesh.configure_chunk_cap(None)
        eb._keystore.invalidate()
        topology.set_default_topology(before)


def _verify(pks, msgs, sigs):
    vid = hashlib.sha256(b"".join(pks)).digest()
    return vid, eb.verify_valset_resident(vid, pks, msgs, sigs)


def test_the_sharded_masks_lanes_are_the_references(plane):
    pks, msgs, sigs, want = _commit(seed=2026, height=7)
    assert sum(want) == N - 2 * SHARDS - 2
    vid, got = _verify(pks, msgs, sigs)
    wrong = [i for i in range(N) if bool(got[i]) != want[i]]
    assert wrong == [], f"lanes that differ from the reference: {wrong}"
    # every chip's slice of every chunk refused exactly its own lane
    for lane in _corrupted_lanes() + [ABSENT, SHORT_KEY]:
        assert not got[lane]

    rv = eb._keystore.entry_for(vid)
    plan = mesh.shard_plan()
    assert plan is not None and plan.n_shards == SHARDS
    assert rv.plan is plan
    assert [(s, e, z) for s, e, z, _ in rv.chunks] == CHUNKS
    assert CHUNKS == mesh.shard_chunks(N, SHARDS, CAP, 64)
    for _, _, size, a_dev in rv.chunks:
        slices = {s.device: s.data.shape for s in a_dev.addressable_shards}
        assert set(slices) == set(plan.mesh.devices.flat)
        assert set(slices.values()) == {(8, size // SHARDS)}
    assert rv.table_dev is None  # no indexed view of sharded rows


def test_the_wire_ledger_books_real_and_padded_lanes_of_the_mesh(plane):
    pks, msgs, sigs, _ = _commit(seed=5, height=8)
    _verify(pks, msgs, sigs)
    assert plane.lanes_by_route() == {"resident": N}
    assert plane.padded_lanes_by_route() == {"resident": 256 + 64}
    snap = plane.snapshot()
    assert snap["padded_lanes"] == {"resident": 320}
    assert {(r["route"], r["bucket"], r["device"])
            for r in snap["profiles"]} == {
        ("resident", 256, "mesh:8"), ("resident", 64, "mesh:8")}
    # a loop that keys its profiles by the per-shard bucket says the total
    plane.note_chunk("sharded", "mesh:8", 32, 200, 1, 0, 0, 0, 0,
                     padded_lanes=256)
    assert plane.padded_lanes_by_route()["sharded"] == 256


def test_launch_and_retire_carry_shards_and_lanes_per_shard(plane):
    pks, msgs, sigs, _ = _commit(seed=6, height=9)
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    with tracelib.use(root):
        _verify(pks, msgs, sigs)
    root.end()
    spans = tracer.recent()[0]["spans"]
    sharded = [{"shards": SHARDS, "lanes_per_shard": 32},
               {"shards": SHARDS, "lanes_per_shard": 8}]
    tags = {name: [s["tags"] for s in spans if s["name"] == name]
            for name in ("resident.launch", "resident.retire")}
    assert tags["resident.retire"] == sharded
    # a launch also says which one it is and how many were in flight
    assert tags["resident.launch"] == [
        dict(t, chunk=k, inflight=k) for k, t in enumerate(sharded)]


def test_a_second_sharded_commit_misses_no_executable(plane):
    pks, msgs, sigs, want = _commit(seed=11, height=10)
    _, first = _verify(pks, msgs, sigs)  # the warm commit
    assert [bool(x) for x in first] == want
    reg = aot.default_registry()
    before = reg.stats()
    pks2, msgs2, sigs2, want2 = _commit(seed=11, height=11)
    assert pks2 == pks and sigs2 != sigs
    _, second = _verify(pks2, msgs2, sigs2)
    assert [bool(x) for x in second] == want2
    after = reg.stats()
    assert after["misses"] == before["misses"]
    assert after["compiles"] == before["compiles"]
    assert len(after["builds"]) == len(before["builds"])
    sharded = [b for b in after["builds"]
               if b["kernel"] == "ed25519.verify_resident" and b["sharded"]]
    assert {b["bucket"] for b in sharded} >= {256, 64}


def test_with_no_shard_plan_the_rows_and_the_launch_stay_on_one_chip(plane):
    """One fault domain (the default [crypto] fault_domains = 1) has no
    shard plan: the same commit runs on the default chip, same mask."""
    topology.set_default_topology(topology.DeviceTopology.single())
    assert mesh.shard_plan() is None
    pks, msgs, sigs, want = _commit(seed=2026, height=7)
    vid, got = _verify(pks, msgs, sigs)
    assert [bool(x) for x in got] == want
    rv = eb._keystore.entry_for(vid)
    assert rv.plan is None
    assert [z for _, _, z, _ in rv.chunks] == [256, 64]
    assert all(len(a.addressable_shards) == 1 for _, _, _, a in rv.chunks)
    assert plane.padded_lanes_by_route() == {"resident": 320}
    assert {r["device"] for r in plane.snapshot()["profiles"]} == {"dev0"}
